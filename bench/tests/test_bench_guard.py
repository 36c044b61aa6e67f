"""Off the chip the benchmark refuses to run: it exits non-zero and prints
no result line, so no CPU timing can pass for a device number."""
import os
import subprocess
import sys

import harness


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", "tiny1m.al-scan", "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode not in (0, None)
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_peaks_table_refuses_unknown_device():
    import pytest
    with pytest.raises(KeyError):
        harness.device_peaks(os.path.join(harness.BENCH, "peaks.json"),
                             "TPU v99")
    v5e = harness.device_peaks(os.path.join(harness.BENCH, "peaks.json"),
                               "TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes"] == 16e9
