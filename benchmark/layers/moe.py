"""Layer: expert layer (``byteps_tpu/parallel/moe.py::dropless_moe_ffn``
inside ``models/olmoe.py``).

From the device trace, first device, line ``XLA Ops``, per traced step:

``moe.gmm_ms``            the grouped matmuls, forward and backward: the
                          kernels the TPU compiler makes of ``lax.ragged_dot``
                          (``%ragged-dot...`` custom calls; their
                          ``ragged-dot-metadata`` helpers count as routing).
``moe.route_ms``          operations under the program's ``bps.moe.route``
                          scope: router, top-k, sort, gather, un-permute and
                          combine, and their gradients.
``moe.layer_share_pct``   those two and what else runs under
                          ``bps.moe.experts`` (the casts of the float32
                          expert weights to bf16, silu x up, gradient sums;
                          ``probes.moe_experts_other_ms``) over the time of
                          the capture's programs on ``XLA Modules``
                          (``probes.moe_programs_ms``): the expert layer's
                          share of the step.
``moe.gmm_roofline_pct``  the least time the chip could take for the grouped
                          matmuls — the larger of ``gmm_flops`` over the peak
                          bf16 rate and ``gmm_bytes`` over the peak HBM rate
                          (``lib/peaks.json``) — over ``moe.gmm_ms``.

An event's name is raw HLO text and says nothing of scopes; the scope is in
the ``tf_op`` stat of the event's *metadata* (``jit(_step)/jvp(OlmoeModel)/
layer_0/moe/bps.moe.route/...``), which ``jax.profiler.ProfileData`` does
not hand out. ``scoped_ops`` therefore reads the ``.xplane.pb`` itself, as
far as it needs to (protobuf wire format, the fields of ``xplane.proto``).

``moe.max_expert_load`` (program counter) comes from a probe before the
window: the first batch routed through the run's own weights with the
``"moe_stats"`` collection mutable, the counts published by
``parallel/moe.py::publish_moe_stats`` (gauge ``bps_moe_max_expert_load``:
the busiest expert's assignments over the mean, worst layer).

A program without the scopes, the kernels or the collection reports
nothing.

    python3 benchmark/layers/moe.py <trace_dir> <steps>

prints the three sums for a capture a traced run left behind.
"""

import json
import os
import re
import sys

LAYER = "expert layer"
ROUTE_SCOPE, EXPERTS_SCOPE = "bps.moe.route", "bps.moe.experts"
GMM_KERNEL = re.compile(r"^%?ragged-dot(?!-metadata)")
METRICS = {
    "moe.gmm_ms": {"unit": "ms", "better": "lower",
                   "source": "device_trace", "moves": "step_ms_p50"},
    "moe.route_ms": {"unit": "ms", "better": "lower",
                     "source": "device_trace", "moves": "step_ms_p50"},
    "moe.layer_share_pct": {"unit": "%", "better": "lower",
                            "source": "device_trace",
                            "moves": "step_ms_p50"},
    "moe.gmm_roofline_pct": {"unit": "%", "better": "higher",
                             "source": "device_trace", "moves": "mfu_pct"},
    "moe.max_expert_load": {"unit": "ratio", "better": "lower",
                            "source": "program_counter",
                            "moves": "tokens_per_s_per_chip"},
}


# --------------------------------------------------------------------------
# What the grouped matmuls of one step need, from shapes alone.

def gmm_calls(layers: int) -> int:
    """gate, up and down, each forward, dgrad and wgrad."""
    return layers * 3 * 3


def gmm_flops(tokens: int, top_k: int, d: int, m: int, layers: int) -> int:
    """Every call multiplies the ``tokens * top_k`` assigned rows — exactly,
    no padding to a tile or a capacity — through one d x m matrix each."""
    return gmm_calls(layers) * 2 * tokens * top_k * d * m


def gmm_bytes(tokens: int, top_k: int, experts: int, d: int, m: int,
              layers: int, operand_bytes: int = 2) -> int:
    """Every call reads two and writes one of: the rows at width d, the rows
    at width m, all experts' d x m weights (or their gradient) — each once,
    in the dtype the kernel reads (bf16)."""
    rows = tokens * top_k
    return gmm_calls(layers) * operand_bytes * (rows * d + rows * m
                                                + experts * d * m)


def gmm_roofline_pct(gmm_ms: float, cfg: dict, tokens: int,
                     peaks: dict) -> float:
    d, m, layers = (cfg["hidden_size"], cfg["intermediate_size"],
                    cfg["num_hidden_layers"])
    k, e = cfg["num_experts_per_tok"], cfg["num_experts"]
    least_s = max(
        gmm_flops(tokens, k, d, m, layers) / peaks["bf16_flops_per_s"],
        gmm_bytes(tokens, k, e, d, m, layers) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (gmm_ms * 1e-3)


# --------------------------------------------------------------------------
# The capture, as far as the scopes need it.

def _fields(buf):
    """(field number, wire type, value) of one protobuf message: varints as
    ints, length-delimited fields as memoryviews, fixed ones skipped."""
    buf, i, n = memoryview(buf), 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value

    while i < n:
        key = varint()
        number, wire = key >> 3, key & 7
        if wire == 0:
            yield number, wire, varint()
        elif wire == 2:
            size = varint()
            yield number, wire, buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def scoped_ops(xplane_path: str, layout, line: str = None) -> list:
    """``(name, tf_op, duration_ps)`` of every event on ``line`` (the
    back-to-back op line, ``layout.sync_line``, unless given) of the first
    device plane."""
    line = line or layout.sync_line
    device_re = re.compile(layout.device_plane)
    with open(xplane_path, "rb") as f:
        space = f.read()
    planes = {}
    for number, _, plane in _fields(space):
        if number != 1:                               # XSpace.planes
            continue
        name = next((_text(v) for n, _, v in _fields(plane) if n == 2), "")
        if device_re.match(name):
            planes[name] = plane
    if not planes:
        return []
    stat_names, metadata, events = {}, {}, []
    for number, _, value in _fields(planes[min(planes)]):
        if number == 5:                               # stat_metadata entry
            entry = dict((n, v) for n, _, v in _fields(value))
            fields = dict((n, v) for n, _, v in _fields(entry.get(2, b"")))
            stat_names[entry.get(1, 0)] = _text(fields.get(2, b""))
        elif number == 4:                             # event_metadata entry
            entry = dict((n, v) for n, _, v in _fields(value))
            name, stats = "", []
            for n, _, v in _fields(entry.get(2, b"")):
                if n == 2:
                    name = _text(v)
                elif n == 5:                          # XEventMetadata.stats
                    stats.append(dict((a, b) for a, _, b in _fields(v)))
            metadata[entry.get(1, 0)] = (name, stats)
        elif number == 3:                             # XLine
            fields = list(_fields(value))
            if any(n == 2 and _text(v) == line for n, _, v in fields):
                for n, _, v in fields:
                    if n == 4:                        # XLine.events
                        event = dict((a, b) for a, _, b in _fields(v))
                        events.append((event.get(1, 0), event.get(3, 0)))
    out = []
    for metadata_id, duration_ps in events:
        name, stats = metadata.get(metadata_id, ("", []))
        tf_op = ""
        for stat in stats:
            if stat_names.get(stat.get(1)) == "tf_op":
                # a string, or a reference to a stat name that holds it
                tf_op = (_text(stat[5]) if 5 in stat
                         else stat_names.get(stat.get(7), ""))
        out.append((name, tf_op, duration_ps))
    return out


def split_ms(ops, steps: int) -> dict:
    """Per step, ms: the grouped-matmul kernels, the route scope, and the
    rest of the experts scope. Empty where none of them shows."""
    sums = {"gmm": 0, "route": 0, "experts_other": 0}
    for name, tf_op, duration_ps in ops:
        if GMM_KERNEL.match(name):
            sums["gmm"] += duration_ps
        elif ROUTE_SCOPE in tf_op or name.startswith("%ragged-dot-metadata"):
            sums["route"] += duration_ps
        elif EXPERTS_SCOPE in tf_op:
            sums["experts_other"] += duration_ps
    if not steps or not any(sums.values()):
        return {}
    return {k: v * 1e-9 / steps for k, v in sums.items()}


# --------------------------------------------------------------------------

def setup(run):
    """The probe: which experts the first batch's tokens reach, with the
    run's own weights."""
    counts_of = getattr(run.config, "expert_counts", None)
    first = getattr(run.config, "FIRST", None)
    if counts_of is None or not first:
        return
    import jax

    from byteps_tpu.parallel.moe import publish_moe_stats

    rows = run.rows // run.chips                      # one chip's batch
    counts = counts_of(run.cfg)(jax.random.PRNGKey(first["seed"]),
                                first["tokens"][:rows])
    published = publish_moe_stats(list(counts))
    run.probes["moe_counts_min_max"] = [int(counts.min()), int(counts.max())]
    run.probes.update(published)


def read(run):
    out = {"moe.max_expert_load":
           run.probes.get("bps_moe_max_expert_load")}
    if run.trace is None:
        return out
    from benchmark.lib import device, trace_reduce

    xplane = trace_reduce.find_xplane(os.path.join(run.out_dir, "trace"))
    steps = run.trace["steps"]
    ms = split_ms(scoped_ops(xplane, run.layout), steps)
    # every program of the capture, as the ops are: step.device_ms leaves
    # out a program that the device's clock starts before the first step
    # span (PERF.md section 7, PR 28)
    programs_ms = sum(d for _, _, d in scoped_ops(
        xplane, run.layout, run.layout.module_line)) * 1e-9 / steps
    if not ms or not programs_ms:
        return out
    run.probes["moe_experts_other_ms"] = ms["experts_other"]
    run.probes["moe_programs_ms"] = programs_ms
    out["moe.route_ms"] = ms["route"]
    out["moe.layer_share_pct"] = 100.0 * sum(ms.values()) / programs_ms
    if ms["gmm"]:
        import jax

        out["moe.gmm_ms"] = ms["gmm"]
        out["moe.gmm_roofline_pct"] = gmm_roofline_pct(
            ms["gmm"], run.cfg, run.rows // run.chips * run.cfg["seq_len"],
            device.peaks(jax.devices()[0].device_kind))
    return out


if __name__ == "__main__":
    sys.path.insert(0, __file__.rsplit("/benchmark/", 1)[0])
    from benchmark.lib import trace_reduce

    print(json.dumps(split_ms(
        scoped_ops(trace_reduce.find_xplane(sys.argv[1]), trace_reduce.TPU),
        int(sys.argv[2]))))
