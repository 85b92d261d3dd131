"""A measurement path that finds no TPU fails: it does not fall back to the
CPU under a device metric's name (an unattached machine silently gives
``CpuDevice``). The CPU spellings (--smoke) are covered by their own runs."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import bench  # noqa: E402


@pytest.mark.parametrize("argv", [
    ["bench.py"],
    ["bench.py", "--model", "gpt2"],
    ["bench_ps.py"],
    ["tools/mfu_attribution.py"],
], ids=lambda a: " ".join(a))
def test_device_bench_refuses_cpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable] + argv, cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "measures the TPU, but JAX found" in out.stderr
    assert "'platform': 'cpu'" in out.stderr
    assert '"metric"' not in out.stdout  # no result line was printed


def test_peaks_table_is_keyed_by_device_kind():
    # v5e as JAX names it; Google Cloud "TPU v5e" documentation figures
    assert bench.DEVICE_PEAKS["TPU v5 lite"] == {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(SystemExit, match="no published peaks for device_kind"):
        bench.device_peaks()  # tier-1 runs on 'cpu': not a default, an error


def test_every_result_line_names_its_device():
    stamp = bench.device_stamp()
    assert stamp["platform"] == "cpu" and stamp["device_count"] >= 1
    assert set(stamp) == {"platform", "device_kind", "device_count"}
