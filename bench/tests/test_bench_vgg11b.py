"""The paper's VGG11B in the benchmark: its configuration file is the
paper's, its operation counts match hand arithmetic, three training steps
agree with the plain reference bit for bit, and the layer map of its step
places ops on every one of its ten blocks."""

from __future__ import annotations

import json

import test_bench_reference
from _tiny import REPO, make_tiny_root
from test_bench_layer_map import _tiny_run

from bench import layer_map, work


def _cfg():
    return json.loads(
        (REPO / "bench" / "configs" / "vgg11b.json").read_text())


def test_config_file_is_the_papers():
    test_bench_reference.test_config_file_is_the_papers("vgg11b")


def test_three_training_steps_bitwise():
    # 1/16 width keeps the grid-5 / window-6 pool of the 32x32 blocks
    test_bench_reference.test_three_training_steps_bitwise("vgg11b")


def test_forward_multiply_adds_per_image():
    # 32²·27·128 + 2·32²·1152·128 + 32²·1152·256 + 16²·2304·256
    # + 16²·2304·512 + 2·8²·4608·512 + 4²·4608·512 + 2048·1024 + 1024·10
    want = (1024 * 27 * 128 + 2 * 1024 * 1152 * 128 + 1024 * 1152 * 256
            + 256 * 2304 * 256 + 256 * 2304 * 512 + 2 * 64 * 4608 * 512
            + 16 * 4608 * 512 + 2048 * 1024 + 1024 * 10)
    assert work.forward_macs_per_image(_cfg()) == want == 1_402_349_568


def test_step_at_batch_128_by_hand():
    batch = 128
    fw = 1_402_349_568 - 1024 * 10     # forward layers, per image
    # learning layers: 32×32×128 pooled on a 5×5 grid (3 blocks), 16×16×256
    # on 4×4 (2 blocks), 8×8×512 and smaller on 2×2 (4 blocks); 1024 → 10
    lr = 3 * 5 * 5 * 128 * 10 + 2 * 4 * 4 * 256 * 10 + 4 * 2 * 2 * 512 * 10 \
        + 1024 * 10
    out = 1024 * 10
    want = 2 * batch * (2 * fw + 3 * lr + 2 * out)
    assert work.step_ops(_cfg(), batch) == want == 718_210_400_256


def test_map_of_the_tiny_step_places_every_block(tmp_path):
    run = _tiny_run(make_tiny_root(tmp_path), "train-vgg11b")
    m = layer_map.placed(run)
    assert len(run.config["blocks"]) == 10
    want = {f"block{i}/{p}" for i in range(10)
            for p in layer_map.BLOCK_PARTS} | {"update", "output"}
    assert {v for v in m.values() if v is not None} == want
