"""Layer: device. The share of the traced window in which no operation ran
on the device (``XLA Ops`` ∪ ``Async XLA Ops``), averaged over the chips
used: the figure the result line's ``busy_s`` / ``window_s`` give."""

LAYER = "device"
METRICS = {
    "device.idle_pct": {"unit": "%", "better": "lower",
                        "source": "device_trace",
                        "moves": "tokens_per_s_per_chip"},
}


def read(run):
    if run.trace is None:
        return {}
    return {"device.idle_pct": 100.0 * run.trace["idle_share"]}
