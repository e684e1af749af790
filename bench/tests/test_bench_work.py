"""The operation counts behind the roofline shares and train_mfu, pinned
against hand arithmetic."""

from __future__ import annotations

import json

from _tiny import REPO

from bench import work


def _cfg(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())


def test_vgg8b_forward_multiply_adds_per_image():
    # 32²·27·128 + 32²·1152·256 + 16²·2304·256 + 16²·2304·512
    # + 8²·4608·512 + 4²·4608·512 + 2048·1024 + 1024·10
    want = (1024 * 27 * 128 + 1024 * 1152 * 256 + 256 * 2304 * 256
            + 256 * 2304 * 512 + 64 * 4608 * 512 + 16 * 4608 * 512
            + 2048 * 1024 + 1024 * 10)
    assert work.forward_macs_per_image(_cfg("vgg8b")) == want == 949_364_736


def test_mlp4_forward_multiply_adds_per_image():
    want = 3072 * 3000 + 3000 * 3000 * 2 + 3000 * 10
    assert work.forward_macs_per_image(_cfg("mlp4")) == want == 27_246_000


def test_tiny_config_training_step_by_hand():
    cfg = {"name": "tiny", "input_shape": [4, 4, 2], "num_classes": 3,
           "kernel_size": 3, "blocks": [
               {"kind": "conv", "width": 4, "pool": True, "dropout": 0.0,
                "d_lr": 16},
               {"kind": "linear", "width": 5, "pool": False, "dropout": 0.0,
                "d_lr": 16}]}
    batch = 2
    conv = 4 * 4 * 9 * 2 * 4          # per image, forward (= grad_W)
    lin = 2 * 2 * 4 * 5                # 2×2×4 pooled features → 5
    # learning layers: conv block pools 2×2×4 to s=2 (16//4=4 → √4=2): 16→3
    lr = 16 * 3 + 5 * 3
    out = 5 * 3
    want = 2 * batch * (2 * conv + 2 * lin + 3 * lr + 2 * out)
    assert work.step_ops(cfg, batch) == want
    calls = work.forward_layer_calls(cfg, batch, kind="conv")
    assert [m for m, _ in calls] == [batch * conv, batch * conv]
    # bytes: x int8, W int16, a int8 (forward); x int8, δ int32, ∇W int32
    # (x is 4·4·2 = 32 values an image, a and δ 4·4·4 = 64, W 9·2·4 = 72)
    fwd_bytes = batch * 32 * 1 + 72 * 2 + batch * 64 * 1
    gw_bytes = batch * 32 * 1 + batch * 64 * 4 + 72 * 4
    assert [b for _, b in calls] == [fwd_bytes, gw_bytes]


def test_roofline_takes_each_calls_larger_bound():
    calls = [(10, 1), (1, 1000)]        # one compute-bound, one memory-bound
    t, compute = work.roofline_seconds(calls, peak_ops=20.0, peak_bytes=100.0)
    assert t == 1.0 + 10.0
    assert compute == 1.0 / 11.0


def test_vgg8b_step_at_batch_128_is_486_gop():
    ops = work.step_ops(_cfg("vgg8b"), 128)
    assert 486e9 < ops < 487e9


def test_kernel_roofline_share_from_a_trace():
    from types import SimpleNamespace

    from bench import trace_reduce

    cfg = _cfg("mlp4")
    calls = work.forward_layer_calls(cfg, 128, kind="linear")
    least, compute = work.roofline_seconds(calls, 393e12, 819e9)
    assert compute < 0.5                    # weight bytes bound MLP4's layers
    # two steps' matmul kernels: two events, each 4× one step's least time
    ns = int(round(least * 4 * 1e9))
    ops = {0: [("nitro_matmul_fwd.1", 0, ns), ("fusion.3", ns, 2 * ns),
               ("nitro_matmul_grad_w.2", 2 * ns, 3 * ns)]}
    spans = [(trace_reduce.WINDOW_SPAN, 0, 4 * ns)]
    run = SimpleNamespace(
        reduced_trace=trace_reduce.reduce_events(ops, spans),
        window={"steps": 2}, config=cfg, mix={"batch": 128}, chips=1,
        devices=[SimpleNamespace(device_kind="TPU v5 lite")])
    share = work.kernel_roofline_share(run, "linear", r"^nitro_matmul")
    assert abs(share - 100.0 * 2 * least / (2 * ns * 1e-9)) < 1e-6
    assert 24.9 < share < 25.1            # 2 steps' least over 8 of them
    assert work.kernel_roofline_share(run, "conv", r"^stream_conv") is None
