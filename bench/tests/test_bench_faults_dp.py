"""The data-parallel cell on four virtual CPU devices: sound, it is
correct; with the gradient exchange left out, it is not."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from _tiny import REPO

SCRIPT = textwrap.dedent("""
    import contextlib, io, json, pathlib, sys
    sys.path[:0] = [{tests!r}, {repo!r}, {src!r}]
    import _tiny
    from repro.parallel import dp
    from _faults import patched
    root = _tiny.make_tiny_root(pathlib.Path({tmp!r}))
    out = {{}}
    for fault in (None, "no_exchange"):
        ctx = (patched(dp, "reduce_gradients", lambda g, axis, method: g)
               if fault else contextlib.nullcontext())
        buf = io.StringIO()
        with ctx, contextlib.redirect_stdout(buf):
            rc, _ = _tiny.run_cell(root, "train-vgg8b-dp4")
        out[str(fault)] = (rc, json.loads(buf.getvalue().splitlines()[-1]))
    print(json.dumps(out))
""")


def test_dp_exchange_left_out_fails_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SCRIPT.format(tests=str(REPO / "bench" / "tests"),
                           repo=str(REPO), src=str(REPO / "src"),
                           tmp=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    rc, sound = out["None"]
    assert rc == 0 and sound["correct"] is True
    assert sound["device"]["count"] == 4
    rc, broken = out["no_exchange"]
    assert rc == 0 and broken["correct"] is False
    assert broken["checks"]["params_differ"]["value"] > 0
