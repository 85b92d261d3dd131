"""byteps_tpu — a TPU-native gradient-synchronization framework.

A brand-new, TPU-first implementation of the capability set of BytePS
(reference: ymjiang/byteps — see SURVEY.md): hierarchical two-level gradient
aggregation (intra-slice ICI collectives via XLA/shard_map + an inter-host
DCN key-value push/pull leg to CPU-only parameter servers), tensor
partitioning, priority-credit scheduling, pluggable gradient compression,
sync and async training modes, a Horovod-style user API, and a multi-role
launcher.

Layout (capability parity with the reference's layer map, SURVEY.md §1):

- ``byteps_tpu.config``     — env-var config system (docs/env.md parity).
- ``byteps_tpu.partition``  — tensor → partition slicing + key assignment.
- ``byteps_tpu.core``       — C++ runtime (DCN van, postoffice, PS server,
                              CPU reducer, priority scheduler, compression
                              codecs) + ctypes bindings (core/ffi.py).
- ``byteps_tpu.jax``        — the flagship JAX plugin (init/push_pull/
                              DistributedOptimizer/broadcast_parameters,
                              collective + PS modes, the serial and the
                              bucketed PS step, sync/async/flax/haiku
                              step builders).
- ``byteps_tpu.torch`` / ``.tensorflow`` / ``.keras`` / ``.mxnet`` —
                              Horovod-compatible framework plugins.
- ``byteps_tpu.parallel``   — mesh construction, hierarchical DP (+ int8
                              quantized), ring/Ulysses sequence parallel,
                              TP, GPipe PP, MoE EP, ZeRO sharding.
- ``byteps_tpu.ops``        — Pallas TPU kernels (flash attention fwd/bwd,
                              sliding window).
- ``byteps_tpu.models``     — flax model zoo (ResNet/VGG/BERT/GPT-2/LLaMA/
                              MoE) used by examples/benchmarks.
- ``byteps_tpu.utils``      — checkpoint/resume (orbax), trace timeline.
- ``byteps_tpu.callbacks``  — Keras-style callbacks for JAX loops.
- ``byteps_tpu.server``     — ``python -m byteps_tpu.server`` runs a CPU PS
                              or the scheduler (reference:
                              byteps/server/__init__.py).
- ``byteps_tpu.launcher``   — ``bpslaunch``-style multi-role launcher.
"""

__version__ = "0.1.0"

from byteps_tpu.config import Config, get_config  # noqa: F401
