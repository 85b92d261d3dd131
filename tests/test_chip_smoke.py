"""chip_smoke.py off the chip: the refusal, and its phases at a tiny size.

The script itself has no size option — it always runs GPT-2 124M at full
width. The tiny size is steered from here, through the ``Size`` its phase
functions take (on-chip-measurement guide §2: rehearse the control flow on
the CPU, Pallas in interpret mode).
"""

import ast
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY = chip_smoke.Size(
    model_kw=dict(num_layers=2, d_model=64, num_heads=4, mlp_dim=128,
                  vocab_size=512, max_len=64),
    seq=32, batch_per_chip=2, flash_shapes=((2, 64, 2, 32),),
    interpret=True, device_plane="/host:CPU")


def _python(*argv, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run([sys.executable, *argv], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_refuses_to_pass_without_a_tpu():
    out = _python(os.path.join(REPO, "chip_smoke.py"))
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "needs a TPU" in out.stderr and "'platform': 'cpu'" in out.stderr
    # it stopped at phase 0: no phase line claims success
    assert '"ok": true' not in out.stdout


def test_every_import_resolves_off_the_chip():
    """Phase 0 and the four-chip phase run only on a TPU, so a lazy import
    there of a module that has left the tree shows nowhere else before the
    chip call (PR 31: ``from bench import device_peaks``)."""
    with open(chip_smoke.__file__) as f:
        tree = ast.parse(f.read())
    modules = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            modules.add(node.module)
    missing = sorted(m for m in modules
                     if importlib.util.find_spec(m) is None)
    assert not missing, missing


@pytest.fixture(scope="module")
def problem():
    return chip_smoke.make_problem(TINY, seed=0, n_chips=len(jax.devices()))


def test_collective_and_flash_phases_tiny(problem, capsys):
    """Phases 1 and 4 on the 8-device CPU mesh: the collective step tracks
    the plain reference, the interpreted kernel tracks float32 attention,
    and each phase prints its JSON line."""
    col = chip_smoke.phase_collective(problem)
    assert col["max_loss_diff"] <= chip_smoke.LOSS_TOL
    assert len(col["losses"]) == 5 and col["losses"][-1] < col["losses"][0]
    flash = chip_smoke.phase_flash(TINY, problem, col["ref_losses"], seed=0)
    assert flash["interpret"] is True
    assert flash["max_loss_diff"] <= chip_smoke.FLASH_LOSS_TOL
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["phase"] for l in lines] == ["collective", "flash"]


def test_loss_check_fails_loudly():
    with pytest.raises(RuntimeError, match="differ from the reference"):
        chip_smoke.check_losses("x", [1.0, 1.5], [1.0, 1.0], tol=0.02)
    with pytest.raises(RuntimeError, match="non-finite"):
        chip_smoke.check_losses("x", [float("nan")], [1.0], tol=0.02)
    with pytest.raises(RuntimeError, match=r"4\.000x the gradient tree"):
        chip_smoke.check_pushed("x", per_step=16 * 100, n_params=100)


def test_count_collectives_by_kind():
    hlo = """
      %ar.1 = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={}
      %ars = f32[8]{0} all-reduce-start(f32[8]{0} %p), replica_groups={}
      %ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)
      %rs = f32[2]{0} reduce-scatter(f32[8]{0} %p), dimensions={0}
      %ag = (f32[2]{0}, f32[8]{0}) all-gather-start(f32[2]{0} %rs)
    """
    assert chip_smoke.count_collectives(hlo) == {
        "all-reduce": 2, "reduce-scatter": 1, "all-gather": 1,
        "all-to-all": 0, "collective-permute": 0}


_PS_DRIVER = """
import json, os, tempfile
import jax
import chip_smoke
from tests.test_chip_smoke import TINY
prob = chip_smoke.make_problem(TINY, 0, len(jax.devices()))
col = chip_smoke.phase_collective(prob)
out = tempfile.mkdtemp()
rec = chip_smoke.phase_ps(prob, col["ref_losses"], col["step_s"], out)
print(json.dumps({"logs": sorted(os.listdir(out + "/ps"))}))
"""


@pytest.mark.ps
def test_ps_phase_fleet_bringup_and_bytes_tiny():
    """Phase 2 in a process of its own (it becomes a PS worker): the fleet
    comes up, the losses are the collective ones, one gradient tree is
    pushed per step, and both children exit 0 leaving their logs."""
    out = _python("-c", _PS_DRIVER)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    ps = next(l for l in lines if l.get("phase") == "ps")
    assert ps["pushed_over_grad_tree"] == 1.0
    assert ps["max_loss_diff"] <= chip_smoke.LOSS_TOL
    assert ps["d2h_gbps"] > 0 and ps["h2d_gbps"] > 0
    assert lines[-1]["logs"] == ["scheduler.log", "server.log"]
