"""No reader: a signpost. ``layers/eshare.py`` reads the router's scope since
PR 70 (``split_ms`` asks for ``bps.moe.router`` before ``bps.moe.route``, so
the router's time is reported as ``eshare.router_ms`` and is not in
``eshare.route_ms``), and the reader that stood here under a cell's own
prefix left with the five other copies of ``eshare.py``.

The path stays because ``docs/monitoring.md`` names it and
``tests/test_doc_paths.py`` holds every path a document names to exist;
neither is a ``benchmark`` PR's to edit. The PR that corrects that sentence
deletes this file: no traffic file names it, ``BENCHMARK.json`` has no entry
of its prefix, and nothing imports it.
"""
