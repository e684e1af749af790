"""The peaks table and the refusal to measure off a chip."""

from __future__ import annotations

import json
import subprocess
import sys

import pytest
from _tiny import REPO

from bench import peaks


def test_v5e_peaks_and_source():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["int8_ops_per_s"] == 393e12
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9
    assert "TPU v5e" in peaks.json.loads(peaks.PEAKS_FILE.read_text())["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def test_harness_refuses_the_cpu(tiny_root, capsys):
    from bench import harness

    rc = harness.main(["--workload", "train-vgg8b", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=tiny_root)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_command_exits_nonzero_with_no_result_off_a_chip():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-mlp4",
         "--seed", "2", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_too_few_chips_refused(tiny_root, capsys):
    import jax

    from bench import harness

    more = len(jax.devices()) + 1
    with pytest.raises(harness.NoAccelerator):
        harness.accelerator(more, require=False)
    bench_file = tiny_root / "BENCHMARK.json"
    bench = json.loads(bench_file.read_text())
    for w in bench["workloads"]:
        w["chips"] = more
    bench_file.write_text(json.dumps(bench))
    rc = harness.main(["--workload", "train-vgg8b-dp4", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=tiny_root,
                      require_accelerator=False)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_compile_counter_sees_a_compilation_only_while_active():
    import jax
    import jax.numpy as jnp

    from bench import harness

    c = harness.CompileCounter.get()
    c.active, c.count = True, 0
    try:
        jax.jit(lambda x: x * 3 + 11)(jnp.arange(7)).block_until_ready()
    finally:
        c.active = False
    assert c.count >= 1
    seen = c.count
    jax.jit(lambda x: x * 5 - 13)(jnp.arange(9)).block_until_ready()
    assert c.count == seen
