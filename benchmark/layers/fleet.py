"""Layer: fleet (``byteps_tpu/server``, ``launcher``). From the spawn of
the scheduler and server children to ``bps.init()`` returning."""

LAYER = "fleet"
METRICS = {
    "fleet.start_s": {"unit": "s", "better": "lower",
                      "source": "host_clock", "moves": "setup_s"},
}


def read(run):
    return {"fleet.start_s": run.timings.get("fleet_start_s")}
