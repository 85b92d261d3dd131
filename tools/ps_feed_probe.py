"""Who sets the pace of a PS round: the feed or the C core?

Run on the chip's machine (its host is what is measured), on the fleet and
with the malloc thresholds of the benchmark's ``ps.1chip`` job:

    chiprun --chips 1 -- python3 tools/ps_feed_probe.py

Five parts, one JSON line each, over the 196 gradient leaf shapes of
``gpt2-124m`` (498 MB of float32):

``instant_feed``  the tree is already on the host and already writable: no
                  ``device_get`` and no copy in the enqueue loop. The round's
                  ``elapsed_us`` is then the C core's own pace; compare it
                  with ``round.elapsed_ms`` of a training step (PERF.md).
``out``           a fresh device tree per repetition: ``copy_to_host_async``
                  on every leaf, then ``np.asarray`` leaf by leaf — when the
                  first leaf and when the last has landed — against one
                  ``jax.device_get`` of the whole tree.
``out_unready``   the same while the program that makes the tree still runs,
                  by issue order and by whether the copies are issued before
                  or after that program's end.
``settle_trace``  ``ps_push_pull`` itself with taps: when leaves are enqueued,
                  settled and put, and how much of the tree was pushed from
                  where it landed (``direct_bytes``) and pulled into buffers
                  of an earlier call (``reused_bytes``): ``stage_stats``.
``back``          the host time of ``jax.device_put`` leaf by leaf against
                  one call on the list, and each until the bytes have landed.

Nothing here is read by a benchmark cell.
"""

import contextlib
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
ROUNDS, WARMUP = 6, 4


def _ms(seconds):
    return round(1e3 * seconds, 3)


def leaf_shapes():
    """The gradient tree's leaf shapes in flatten order, from the benchmark's
    own configuration files."""
    import jax
    from benchmark.lib import cell

    cfg = cell.load_json(os.path.join(REPO, "benchmark/configs/gpt2-124m.json"))
    config = cell.load_module(
        os.path.join(REPO, "benchmark/configs/gpt2-124m.py"), "probe_config")
    init, _ = config.build(cfg)
    tree = jax.eval_shape(init, jax.random.PRNGKey(0))
    return [l.shape for l in jax.tree_util.tree_leaves(tree)]


def _slow_tree(shapes):
    """``make(key)`` → the tree, out of a program of ~100 ms of matmuls: as
    a step's gradients, not ready when the PS leg is entered."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        x = jax.random.normal(key, (4096, 4096), jnp.bfloat16)
        for _ in range(120):
            x = jnp.tanh(x @ x * 0.01)
        bias = x[0, 0].astype(jnp.float32)
        return [jnp.full(s, 1.0, jnp.float32) + bias for s in shapes]

    return make


def instant_feed(shapes):
    import numpy as np
    import byteps_tpu.jax as bps
    from byteps_tpu.core import ffi
    from byteps_tpu.jax import ps

    client = bps._st().ps_client
    host = [np.full(s, 1.0, np.float32) for s in shapes]
    assert all(a.flags.writeable and a.flags.c_contiguous for a in host)
    tids = ps.bind("probe", host).tids
    feed, whole = [], []
    before = ffi.round_summary()["completed_total"]
    # one more than is read: a round is closed by the next one's start
    for _ in range(WARMUP + ROUNDS + 1):
        t0 = time.perf_counter()
        handles = [client.push_pull(tid, arr, average=True)
                   for tid, arr in zip(tids, host)]
        t1 = time.perf_counter()
        for h in handles:
            client.wait(h)
        feed.append(t1 - t0)
        whole.append(time.perf_counter() - t0)
    summary = ffi.round_summary()
    assert summary["completed_total"] - before == WARMUP + ROUNDS
    rounds = summary["rounds"][-ROUNDS:]
    return {
        "part": "instant_feed", "leaves": len(host),
        "bytes": sum(a.nbytes for a in host),
        "feed_ms": [_ms(t) for t in feed[WARMUP:-1]],
        "enqueue_to_settled_ms": [_ms(t) for t in whole[WARMUP:-1]],
        "elapsed_us": [r["elapsed_us"] for r in rounds],
        "round_medians_us": {
            k: statistics.median(r[k] for r in rounds)
            for k in rounds[0] if k != "round"}}


def out_half(shapes, repeats=5):
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def make(key):
        return [jnp.full(s, 1.0, jnp.float32) + jax.random.uniform(key, ())
                for s in shapes]

    streamed, batched, read_only = [], [], 0
    for i in range(repeats):
        tree = jax.block_until_ready(make(jax.random.PRNGKey(i)))
        t0 = time.perf_counter()
        for leaf in tree:
            leaf.copy_to_host_async()
        t_issued = time.perf_counter()
        first = np.asarray(tree[0])
        t_first = time.perf_counter()
        host = [np.asarray(leaf) for leaf in tree]
        t_last = time.perf_counter()
        read_only = sum(not a.flags.writeable for a in host)
        streamed.append((t_issued - t0, t_first - t0, t_last - t0))
        del tree, host, first
        tree = jax.block_until_ready(make(jax.random.PRNGKey(100 + i)))
        t0 = time.perf_counter()
        host = jax.device_get(tree)
        batched.append(time.perf_counter() - t0)
        del tree, host
    return {"part": "out", "read_only_leaves": read_only,
            "issue_ms": [_ms(t[0]) for t in streamed],
            "first_leaf_ms": [_ms(t[1]) for t in streamed],
            "last_leaf_ms": [_ms(t[2]) for t in streamed],
            "device_get_ms": [_ms(t) for t in batched]}


def out_unready(shapes, repeats=3):
    """The step's own situation: the copies are wanted while the program
    that makes the tree is still running (here ~100 ms of matmuls). When,
    after that program's end, do the first and the last leaf land — by the
    order in which the copies were issued, and issued before or after the
    program has ended? ``lands_ms`` are leaves 0, 1, 49, 99, 149, 194, 195
    taken in declaration order."""
    import jax
    import numpy as np

    make = _slow_tree(shapes)
    jax.block_until_ready(make(jax.random.PRNGKey(0)))
    marks = (0, 1, 49, 99, 149, 194, 195)

    def take(tree, t_ready):
        lands = []
        for i, leaf in enumerate(tree):
            np.asarray(leaf)
            if i in marks:
                lands.append(_ms(time.perf_counter() - t_ready))
        return lands

    def issue_unready(order):
        def run(tree):
            t0 = time.perf_counter()
            for leaf in order(tree):
                leaf.copy_to_host_async()
            issued = time.perf_counter() - t0
            tree[0].block_until_ready()
            return time.perf_counter(), issued, take
        return run

    def issue_ready(tree):
        tree[0].block_until_ready()
        t_ready = time.perf_counter()
        for leaf in tree:
            leaf.copy_to_host_async()
        return t_ready, time.perf_counter() - t_ready, take

    def issue_windowed(tree, window=8):
        tree[0].block_until_ready()
        t_ready = time.perf_counter()
        for leaf in tree[:window]:
            leaf.copy_to_host_async()

        def take_windowed(tree, t_ready):
            lands = []
            for i, leaf in enumerate(tree):
                if i + window < len(tree):
                    tree[i + window].copy_to_host_async()
                np.asarray(leaf)
                if i in marks:
                    lands.append(_ms(time.perf_counter() - t_ready))
            return lands
        return t_ready, time.perf_counter() - t_ready, take_windowed

    out = {"part": "out_unready"}
    for name, variant in (("unready_forward", issue_unready(list)),
                          ("unready_reversed", issue_unready(reversed)),
                          ("ready_forward", issue_ready),
                          ("ready_windowed8", issue_windowed)):
        runs = []
        for i in range(repeats):
            t_dispatch = time.perf_counter()
            tree = make(jax.random.PRNGKey(i + 1))
            t_ready, issued, taker = variant(tree)
            runs.append({"program_ms": _ms(t_ready - t_dispatch),
                         "issue_ms": _ms(issued),
                         "lands_ms": taker(tree, t_ready)})
            del tree
        out[name] = runs
    return out


def settle_trace(shapes, repeats=4):
    """``ps.ps_push_pull`` itself on a tree whose program still runs, with
    taps on the client and on ``jax.device_put``: when, from the call's
    start, each marked leaf was enqueued, settled (its ``wait`` returned)
    and put, and what the puts cost in all."""
    import jax
    import byteps_tpu.jax as bps
    from byteps_tpu.jax import ps

    make = _slow_tree(shapes)
    marks = (0, 1, 49, 99, 149, 193, 194, 195)
    st, real_put = bps._st(), jax.device_put
    real = st.ps_client
    log = {}

    class Tap:
        def __getattr__(self, name):
            return getattr(real, name)

        def push_pull(self, *args, **kwargs):
            h = real.push_pull(*args, **kwargs)
            log["enqueue"].append(time.perf_counter())
            return h

        def wait(self, h):
            t = time.perf_counter()
            real.wait(h)
            log["settle"].append(time.perf_counter())
            log["waited"] += log["settle"][-1] - t

    def put(x):
        t = time.perf_counter()
        out = real_put(x)
        log["put"].append(time.perf_counter())
        log["put_cost"] += log["put"][-1] - t
        return out

    runs = []
    st.ps_client, jax.device_put = Tap(), put
    try:
        for i in range(WARMUP + repeats):
            tree = make(jax.random.PRNGKey(i + 1))
            log.update(enqueue=[], settle=[], put=[], waited=0.0, put_cost=0.0)
            t0 = time.perf_counter()
            out = ps.ps_push_pull(tree, average=True, prefix="probe_tree")
            t_return = time.perf_counter()
            jax.block_until_ready(out)
            t_landed = time.perf_counter()
            runs.append({
                **{k: [_ms(log[k][m] - t0) for m in marks]
                   for k in ("enqueue", "settle", "put")},
                "waited_ms": _ms(log["waited"]),
                "put_cost_ms": _ms(log["put_cost"]),
                "return_ms": _ms(t_return - t0),
                "landed_ms": _ms(t_landed - t0),
                "put_stats": dict(ps.put_stats),
                "stage_stats": dict(ps.stage_stats)})
            del tree, out
    finally:
        st.ps_client, jax.device_put = real, real_put
    return {"part": "settle_trace", "marks": marks, "runs": runs[WARMUP:]}


def back_half(shapes, repeats=5):
    import jax
    import numpy as np

    def timed(put):
        host = [np.full(s, 1.0, np.float32) for s in shapes]
        t0 = time.perf_counter()
        devs = put(host)
        t1 = time.perf_counter()
        jax.block_until_ready(devs)
        return t1 - t0, time.perf_counter() - t0

    out = {"part": "back"}
    for name, put in (("list", jax.device_put),
                      ("per_leaf", lambda h: [jax.device_put(a) for a in h])):
        runs = [timed(put) for _ in range(repeats)]
        out[f"{name}_dispatch_ms"] = [_ms(r[0]) for r in runs]
        out[f"{name}_landed_ms"] = [_ms(r[1]) for r in runs]
    return out


def main() -> int:
    from benchmark.lib import cell, fleet
    from byteps_tpu.core.build import build

    traffic = cell.load_json(os.path.join(REPO,
                                          "benchmark/traffic/ps.1chip.json"))
    fleet.steady_malloc(traffic["malloc"], os.environ)
    build(verbose=False)
    out_dir = os.path.join(REPO, "chiprun_out", "ps_feed_probe")
    with contextlib.ExitStack() as job:
        job.enter_context(fleet.ps_fleet(REPO, os.path.join(out_dir, "fleet"),
                                         traffic["fleet"], traffic["env"]))
        import jax
        import byteps_tpu.jax as bps

        bps.init()
        job.callback(bps.shutdown)
        d = jax.devices()[0]
        print(json.dumps({"platform": d.platform, "kind": d.device_kind,
                          "count": jax.device_count()}), flush=True)
        shapes = leaf_shapes()
        for part in (instant_feed, out_half, out_unready, settle_trace, back_half):
            print(json.dumps(part(shapes)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
