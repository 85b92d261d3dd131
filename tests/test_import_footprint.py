"""What ``import byteps_tpu.jax, byteps_tpu.models`` loads: every cell of
the benchmark pays for it before its first step (``setup_s``).

``byteps_tpu.jax`` imports ``byteps_tpu.parallel``, which imports
``parallel/moe.py``; ``byteps_tpu.models`` imports every model. So a kernel
library at the top of either file is loaded by every user. The rule (PERF.md
section 6, PR 28): a kernel library (Pallas, megablox) is imported inside
the function that calls it, as ``models/transformer.py::_attention_fn`` does
for the flash kernel; the expert layer's and the sparse attention's four
modules import at module level only what was loaded before they existed
(and each other); so does the looped model's.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, sys
import byteps_tpu.jax, byteps_tpu.models
imported = set(sys.modules)

import jax, jax.numpy as jnp, numpy as np
from byteps_tpu.models import (KeyeTiny, MellumTiny, NemotronHTiny, OlmoeTiny,
                               OuroTiny, Phi4FlashTiny, keye_loss, mellum_loss,
                               nemotron_h_loss, olmoe_loss, ouro_loss,
                               phi4_flash_loss)
for tiny, loss, seq in ((OlmoeTiny, olmoe_loss, 16), (KeyeTiny, keye_loss, 32),
                        (OuroTiny, lambda out, tokens: ouro_loss(out), 16),
                        (MellumTiny, lambda out, tokens: mellum_loss(out), 16),
                        (NemotronHTiny,
                         lambda out, tokens: nemotron_h_loss(out), 16),
                        (Phi4FlashTiny,
                         lambda out, tokens: phi4_flash_loss(out), 16)):
    model = tiny()
    tokens = np.zeros((1, seq), np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    jax.grad(lambda p: loss(model.apply(p, tokens), tokens))(params)
print(json.dumps({"imported": sorted(imported),
                  "by_the_model": sorted(set(sys.modules) - imported)}))
"""

KERNEL_LIBRARIES = ("jax.experimental.pallas", "jax._src.pallas",
                    "jax.experimental.mosaic", "byteps_tpu.ops")


@pytest.fixture(scope="module")
def loaded():
    out = subprocess.run(
        [sys.executable, "-c", CHILD], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _kernel_modules(names):
    return [n for n in names if n.startswith(KERNEL_LIBRARIES)]


def test_importing_the_library_loads_no_kernel_library(loaded):
    for module in ("byteps_tpu.models.olmoe", "byteps_tpu.parallel.moe",
                   "byteps_tpu.models.keye",
                   "byteps_tpu.parallel.sparse_attention",
                   "byteps_tpu.models.ouro", "byteps_tpu.models.mellum",
                   "byteps_tpu.models.nemotron_h",
                   "byteps_tpu.models.phi4_flash"):
        assert module in loaded["imported"]
    assert _kernel_modules(loaded["imported"]) == []


def test_the_expert_model_loads_only_what_its_grouped_matmul_needs(loaded):
    """``lax.ragged_dot`` and ``lax.top_k`` need nothing beyond jax itself:
    building the tiny OlmoeModel and the tiny KeyeModel (sparse attention,
    a share of the experts), applying them and taking their gradients loads
    no kernel library and nothing of byteps_tpu that the import had not
    loaded; nor does the tiny OuroModel, whose loop is flax's own scan, nor
    the tiny MellumModel (windowed and global attention, a share of the
    experts with no shared one), nor the tiny NemotronHModel (the
    state-space scan in XLA, ungated experts, attention with no
    rotation), nor the tiny Phi4FlashModel (the selective scan, differential
    attention, the handed memory and key-value pair)."""
    new = loaded["by_the_model"]
    assert _kernel_modules(new) == []
    assert [n for n in new if n.startswith("byteps_tpu")] == []


# What the two modules may import at module level: what `import
# byteps_tpu.jax, byteps_tpu.models` loaded before they existed.
ALLOWED = {"__future__", "functools", "typing", "jax", "jax.numpy",
           "jax.ad_checkpoint",    # re-exports what `import jax` loaded
           "flax.linen", "byteps_tpu.jax._compat", "byteps_tpu.models.llama",
           "byteps_tpu.models.transformer", "byteps_tpu.parallel.moe",
           "byteps_tpu.parallel.sparse_attention"}


@pytest.mark.parametrize("path", ("byteps_tpu/parallel/moe.py",
                                  "byteps_tpu/models/olmoe.py",
                                  "byteps_tpu/parallel/sparse_attention.py",
                                  "byteps_tpu/models/keye.py",
                                  "byteps_tpu/models/ouro.py"))
def test_module_level_imports_are_the_ones_every_cell_already_paid(path):
    with open(os.path.join(REPO, path)) as f:
        tree = ast.parse(f.read())
    top = []
    for node in tree.body:
        if isinstance(node, ast.Import):
            top += [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            top.append(node.module)
    assert top and set(top) <= ALLOWED, sorted(set(top) - ALLOWED)


@pytest.mark.parametrize("name", sorted(
    n for n in os.listdir(os.path.join(REPO, "byteps_tpu", "ops"))
    if n.endswith(".py")))
def test_a_kernel_module_imports_nothing_of_the_layer_that_calls_it(name):
    """``byteps_tpu/ops`` is the bottom layer: ``parallel/`` and ``models/``
    import a kernel inside the function that calls it, and no kernel file
    imports them back, at module level or inside a function (a cycle, and
    the whole ``parallel`` package for whoever imports the kernel alone)."""
    with open(os.path.join(REPO, "byteps_tpu", "ops", name)) as f:
        tree = ast.parse(f.read())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            imported.append(node.module or "")
    above = ("byteps_tpu.parallel", "byteps_tpu.models", "byteps_tpu.jax")
    assert not [m for m in imported if m.startswith(above)]
