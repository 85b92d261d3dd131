"""Examples-as-smoke-tests (reference test strategy, SURVEY.md §4:
example scripts double as CI smoke tests)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # every test spawns fleets + fresh jax imports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EX = os.path.join(REPO, "example")


def _run(script, *cli, extra_env=None, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
    env.update(extra_env or {})
    out = subprocess.run([sys.executable, script, *cli], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


def test_benchmark_resnet18_smoke():
    out = _run(os.path.join(EX, "jax", "benchmark_byteps.py"),
               "--model", "resnet18", "--batch-size", "8",
               "--image-size", "32", "--num-iters", "2", "--num-warmup", "1",
               "--fp32")
    assert "Iter throughput" in out


def test_benchmark_gpt2_smoke():
    out = _run(os.path.join(EX, "jax", "benchmark_byteps.py"),
               "--model", "gpt2", "--batch-size", "8", "--seq-len", "16",
               "--num-iters", "2", "--num-warmup", "1", "--fp32")
    assert "Iter throughput" in out


def test_mnist_example(tmp_path):
    out = _run(os.path.join(EX, "jax", "mnist_byteps.py"),
               "--epochs", "2", "--batch-size", "512",
               "--ckpt-dir", str(tmp_path / "ck"))
    assert "train accuracy" in out
    # the synthetic task is separable; training must actually learn
    acc = float(out.strip().split("train accuracy:")[-1])
    assert acc > 0.5, out


def test_imagenet_style_example(tmp_path):
    # jax compile dominates; give headroom for parallel (-n) runs
    out = _run(os.path.join(EX, "jax", "train_imagenet_resnet50_byteps.py"),
               "--steps", "3", "--batch-size", "8", "--image-size", "64",
               "--ckpt-every", "2", "--ckpt-dir", str(tmp_path / "ck"),
               timeout=900)
    assert "step 0" in out
    assert os.path.isdir(str(tmp_path / "ck"))


@pytest.mark.ps
def test_torch_benchmark_under_launcher():
    from tests.ps_utils import free_port

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DMLC_PS_ROOT_PORT"] = str(free_port())
    out = subprocess.run(
        [sys.executable, "-m", "byteps_tpu.launcher", "--local", "2",
         "--num-servers", "1", "--",
         sys.executable, os.path.join(EX, "torch", "benchmark_byteps.py"),
         "--num-iters", "3", "--layers", "2", "--hidden", "256"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "throughput" in out.stdout


@pytest.mark.ps
def test_tf_synthetic_benchmark_under_launcher():
    from tests.ps_utils import free_port

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DMLC_PS_ROOT_PORT"] = str(free_port())
    out = subprocess.run(
        [sys.executable, "-m", "byteps_tpu.launcher", "--local", "2",
         "--num-servers", "1", "--",
         sys.executable,
         os.path.join(EX, "tensorflow", "synthetic_benchmark.py"),
         "--num-iters", "3", "--layers", "2", "--hidden", "128"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "throughput" in out.stdout


@pytest.mark.ps
def test_keras_mnist_under_launcher():
    from tests.ps_utils import free_port

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DMLC_PS_ROOT_PORT"] = str(free_port())
    out = subprocess.run(
        [sys.executable, "-m", "byteps_tpu.launcher", "--local", "2",
         "--num-servers", "1", "--",
         sys.executable, os.path.join(EX, "keras", "keras_mnist.py"),
         "--epochs", "2", "--samples", "512"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "final accuracy" in out.stdout


@pytest.mark.ps
def test_torch_mnist_under_launcher():
    from tests.ps_utils import free_port

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DMLC_PS_ROOT_PORT"] = str(free_port())
    out = subprocess.run(
        [sys.executable, "-m", "byteps_tpu.launcher", "--local", "2",
         "--num-servers", "1", "--",
         sys.executable, os.path.join(EX, "torch", "train_mnist_byteps.py"),
         "--epochs", "4", "--samples", "512"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "final accuracy" in out.stdout
    acc = float(out.stdout.strip().split("final accuracy:")[-1])
    assert acc > 0.5, out.stdout


def test_llama_long_context_example():
    out = _run(os.path.join(EX, "jax", "train_llama_long_context.py"),
               "--seq-len", "256", "--steps", "2", "--layers", "2",
               "--d-model", "64", "--heads", "4", "--kv-heads", "2",
               "--vocab", "512", "--fp32")
    assert "tokens/sec" in out


def test_llama_long_context_example_sequence_parallel():
    """--sp: ring attention over the 8-device ici axis + SP-aware loss."""
    out = _run(os.path.join(EX, "jax", "train_llama_long_context.py"),
               "--seq-len", "256", "--steps", "2", "--layers", "2",
               "--d-model", "64", "--heads", "4", "--kv-heads", "2",
               "--vocab", "512", "--fp32", "--sp")
    assert "sp=8xring" in out, out


@pytest.mark.ps
def test_gpt2_compression_e2e_under_launcher():
    """BASELINE config 3 end-to-end: the GPT-2-class LM trains over the
    PS fleet with the C-core codecs. Asserts the measured contract —
    onebit+EF shrinks both wire legs >8x vs uncompressed while the final
    loss stays in family, and topk shrinks bytes too."""
    from tests.ps_utils import free_port

    script = os.path.join(EX, "jax", "train_gpt2_compression_byteps.py")

    def run(compressor):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["DMLC_PS_ROOT_PORT"] = str(free_port())
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-m", "byteps_tpu.launcher", "--local", "2",
             "--num-servers", "1", "--",
             sys.executable, script,
             "--model", "tiny", "--steps", "25", "--json"]
            + (["--compressor", compressor] if compressor else []),
            env=env, capture_output=True, text=True, timeout=420)
        assert out.returncode == 0, out.stdout + out.stderr
        # Workers' stdout interleaves under the launcher — two JSON
        # objects can land on one line. Scan with raw_decode.
        dec = json.JSONDecoder()
        text = out.stdout
        i = text.find("{")
        while i != -1:
            try:
                obj, end = dec.raw_decode(text, i)
            except json.JSONDecodeError:
                i = text.find("{", i + 1)
                continue
            if isinstance(obj, dict) and "final_loss" in obj:
                return obj
            i = text.find("{", end)
        raise AssertionError(f"no result JSON in output:\n{text}")

    base = run("")
    onebit = run("type=onebit;ef=vanilla")
    # topk is paired with error feedback (as in the reference) and k is
    # sized to the model: the embed table has 65k gradient elements, so a
    # tiny k transmits well under 1% of coordinates per step and 25 steps
    # cannot converge regardless of EF. k=4096 (~6%) learns while still
    # shrinking the wire severalfold.
    topk = run("type=topk;k=4096;ef=vanilla")

    assert base["wire_sent_mb"] > 8 * onebit["wire_sent_mb"], (base, onebit)
    assert base["wire_recv_mb"] > 8 * onebit["wire_recv_mb"], (base, onebit)
    assert base["wire_sent_mb"] > 2 * topk["wire_sent_mb"], (base, topk)
    # Convergence: compressed training must still learn the task hard
    # (initial loss ~6.2; dense reaches ~0.09). Lossy codecs trade some
    # step-efficiency for wire bytes, so the bound is absolute, not
    # dense-parity.
    assert onebit["final_loss"] < 1.2, (base, onebit)
    # topk+EF converges but trails the dense run at this step count (EF
    # re-injects dropped mass with delay): require strong learning from
    # the ~6.2 initial loss rather than parity with the 0.09 dense loss.
    assert topk["final_loss"] < 1.2, (base, topk)


@pytest.mark.ps
@pytest.mark.slow
def test_half_wire_composes_with_codec_under_launcher():
    """Regression for the config BASELINE's 345M chip bench uses: a bf16
    wire plus a lossy fleet codec used to fail-stop at declare (codecs
    are float32-domain). The bridge's per-leaf wire plan now declares
    half leaves f32 and upcasts after D2H — the combined run must train
    AND ship onebit-sized wire bytes, not bf16-sized."""
    from tests.ps_utils import free_port

    script = os.path.join(EX, "jax", "train_gpt2_compression_byteps.py")

    def run(extra_cli):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["DMLC_PS_ROOT_PORT"] = str(free_port())
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-m", "byteps_tpu.launcher", "--local", "1",
             "--num-servers", "1", "--",
             sys.executable, script,
             "--model", "tiny", "--steps", "10", "--wire", "bf16",
             "--json"] + extra_cli,
            env=env, capture_output=True, text=True, timeout=420)
        assert out.returncode == 0, out.stdout + out.stderr
        for ln in out.stdout.splitlines():
            if ln.strip().startswith("{") and "final_loss" in ln:
                return json.loads(ln.strip())
        raise AssertionError(f"no result JSON:\n{out.stdout}")

    dense = run([])
    onebit = run(["--compressor", "type=onebit;ef=vanilla"])
    # bf16-dense wire for this model is ~2x smaller than f32; onebit on
    # top must still cut it >8x more in each direction.
    assert dense["wire_sent_mb"] > 8 * onebit["wire_sent_mb"], (dense,
                                                                onebit)
    assert dense["wire_recv_mb"] > 8 * onebit["wire_recv_mb"], (dense,
                                                                onebit)
    assert onebit["final_loss"] < dense["final_loss"] + 2.5, (dense,
                                                              onebit)


@pytest.mark.ps
def test_van_microbench_multiworker_topology():
    """--workers/--servers spawn a real w x s fleet and each worker
    reports its goodput."""
    out = subprocess.run(
        [sys.executable, os.path.join(EX, "microbench_van.py"),
         "--mb", "1", "--tensors", "4", "--rounds", "2",
         "--workers", "2", "--servers", "2"],
        env={**os.environ,
             "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")},
        capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = [l for l in out.stdout.splitlines() if "goodput" in l]
    assert len(lines) == 2, out.stdout  # one JSON line per worker
