"""Layer: build and cache (``core/build.py``, ``utils/compile_cache.py``).

``setup.compile_s``: the first call of the cell's step — tracing, lowering,
compilation or the load from the persistent cache, and the first execution.
``setup.ccore_build_s``: ``core/build.py``'s time in this run; near 0 when
its stamp proves the library current."""

LAYER = "build and cache"
METRICS = {
    "setup.compile_s": {"unit": "s", "better": "lower",
                        "source": "host_clock", "moves": "setup_s"},
    "setup.ccore_build_s": {"unit": "s", "better": "lower",
                            "source": "host_clock", "moves": "setup_s"},
}


def read(run):
    return {"setup.compile_s": run.timings.get("compile_s"),
            "setup.ccore_build_s": run.timings.get("ccore_build_s")}
