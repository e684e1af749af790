"""The map from a traced op's name to a layer of the LES step, and the
four per-layer shares read through it: on hand-written HLO text, on
hand-made traces, and on the map of a real step compiled on the CPU."""

from __future__ import annotations

import json

import pytest
from _tiny import run_cell

from bench import harness, layer_map, trace_reduce

NS = 1e-9

# Optimized HLO as ``compiled.as_text()`` prints it (cut short): a fusion
# of the update, a Pallas kernel, a copy with no metadata, the
# data-parallel exchange, an op under two scopes, and a computation that a
# fusion calls.
HLO = r"""
HloModule jit_step, entry_computation_layout={(s32[8]{0})->s32[8]{0}}

%fused_computation.7 (param_0.1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  ROOT %sub.1 = s32[8]{0} subtract(%param_0.1, %param_0.1), metadata={op_name="jit(step)/update/sub"}
}

ENTRY %main.9 (state_params__w__.1: s32[8]) -> s32[8] {
  %state_params__w__.1 = s32[8]{0} parameter(0), metadata={op_name="state.params[\'w\']"}
  %copy.5 = s32[8]{0:T(128)} copy(%state_params__w__.1)
  %nitro_matmul_grad_w.3 = s32[8]{0} custom-call(%copy.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/block0/backward/jit(nitro_matmul_grad_w)/pallas_call" stack_frame_id=31}
  %pad_add_fusion.1 = s32[8]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(step)/block2/forward/jit(nitro_matmul_fwd)/concatenate" stack_frame_id=30}
  %all-reduce.27 = s32[8]{0} all-reduce(%nitro_matmul_grad_w.3), replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(step)/jit(shard_map)/dp/reduce_gradients/psum"}
  %fusion.1 = s32[8]{0} fusion(%all-reduce.27), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(step)/update/sub" stack_frame_id=113}
  %add.3 = s32[8]{0} add(%fusion.1, %fusion.1), metadata={op_name="jit(step)/block1/local_loss/jit(f)/output/add"}
  %reduce.2 = s32[] reduce(%add.3), metadata={op_name="jit(step)/jit(_threefry_split)/forward/reduce"}
  ROOT %fusion.18 = s32[8]{0} fusion(%add.3), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(step)/block11/local_loss/sub"}
}
"""


def test_parse_places_each_instruction_on_its_innermost_scope():
    m = layer_map.parse_hlo(HLO)
    assert m["fusion.1"] == m["sub.1"] == layer_map.UPDATE
    assert m["nitro_matmul_grad_w.3"] == "block0/backward"
    assert m["pad_add_fusion.1"] == "block2/forward"
    assert m["all-reduce.27"] == layer_map.REDUCE_GRADIENTS
    assert m["add.3"] == layer_map.OUTPUT  # inside block1/local_loss
    assert m["fusion.18"] == "block11/local_loss"
    # no metadata, or a path that names no scope of the step: unplaced
    assert m["copy.5"] is None
    assert m["state_params__w__.1"] is None
    assert m["reduce.2"] is None  # the PRNG's own "forward" is no block
    assert "main.9" not in m and "fused_computation.7" not in m


def test_a_map_names_the_step_only_through_its_own_scopes():
    assert layer_map.names_the_step(layer_map.parse_hlo(HLO))
    # a program older than the step's scopes opens the exchange's alone
    assert not layer_map.names_the_step(
        {"all-reduce.27": layer_map.REDUCE_GRADIENTS, "copy.5": None})
    assert not layer_map.names_the_step({})


MAP = {"fusion.1": "update", "stream_conv_fwd.7": "block0/forward",
       "pad.12": "block0/forward", "copy.3": "block1/backward",
       "stream_conv_grad_w.7": "block1/backward",
       "dot.4": "block1/local_loss", "add.9": "output",
       "all-reduce.27": "reduce_gradients", "copy.8": None}


class _Run:
    def __init__(self, trace, mapping=MAP):
        self.reduced_trace = trace
        setattr(self, layer_map._CACHE_ATTR, mapping)


def _trace():
    # window 100 ns; two chips.  "mystery.2" is not in the map.
    ops = {
        0: [("fusion.1", 0, 30), ("stream_conv_fwd.7", 30, 50),
            ("pad.12", 50, 55), ("copy.3", 55, 60), ("dot.4", 60, 64),
            ("add.9", 64, 66), ("copy.8", 66, 70), ("mystery.2", 70, 72),
            ("all-reduce.27", 72, 80)],
        1: [("fusion.1", 0, 10), ("stream_conv_grad_w.7", 10, 40),
            ("dot.4", 40, 44), ("mystery.2", 44, 50)],
    }
    return trace_reduce.reduce_events(
        ops, [(trace_reduce.WINDOW_SPAN, 0, 100)])


READERS = {
    "integer_sgd_update_share": (30 + 10) / 2,
    "local_loss_share": (4 + 4) / 2,
    "block_glue_share": (5 + 5) / 2,     # pad.12 and copy.3, no kernel
    "layer_unplaced_share": (4 + 2 + 6) / 2,  # copy.8 and the absent op
}


def _reader(name):
    return harness.load_module(
        harness.DEFAULT_ROOT / "bench" / "metrics" / f"{name}.py",
        "bench_metric_" + name)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_made_trace(name):
    got = _reader(name).read(_Run(_trace()))
    assert got == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_nothing_without_ops_or_map(name):
    reader = _reader(name)
    assert reader.read(_Run(None)) is None
    empty = trace_reduce.reduce_events({0: []},
                                       [(trace_reduce.WINDOW_SPAN, 0, 10)])
    assert reader.read(_Run(empty)) is None
    assert reader.read(_Run(_trace(), mapping=None)) is None


def test_layer_seconds_split_kernels_from_the_rest():
    by = layer_map.layer_seconds(_trace(), MAP)
    assert by["block0/forward"] == pytest.approx(
        {"kernels": 20 / 2 * NS, "other": 5 / 2 * NS})
    assert by["block1/backward"] == pytest.approx(
        {"kernels": 30 / 2 * NS, "other": 5 / 2 * NS})
    assert by["unplaced"] == pytest.approx(
        {"kernels": 0.0, "other": 12 / 2 * NS})
    total = sum(v for d in by.values() for v in d.values())
    assert total == pytest.approx(_trace().op_seconds())


def _tiny_run(root, cell):
    bench, workload, config, mix = harness.find_cell(root, cell)
    run = harness.Run(root=root, bench=bench, workload=workload,
                      config=config, mix=mix, seed=3_000_000_017,
                      seconds=0.1, trace=True, t0=0.0)
    run.devices = harness.accelerator(1, require=False)
    return run


@pytest.fixture(scope="module")
def mlp_map(tmp_path_factory):
    from _tiny import make_tiny_root

    root = make_tiny_root(tmp_path_factory.mktemp("layer_map"))
    run = _tiny_run(root, "train-mlp4")
    return run, layer_map.placed(run)


def test_map_of_the_tiny_mlp4_step_places_every_layer(mlp_map):
    run, m = mlp_map
    assert layer_map.placed(run) is m  # built once per run
    blocks = len(run.config["blocks"])
    want = {f"block{i}/{p}" for i in range(blocks)
            for p in layer_map.BLOCK_PARTS} | {"update", "output"}
    assert {v for v in m.values() if v is not None} == want


def test_readers_on_the_real_map(mlp_map):
    """A trace whose op names come from the compiled tiny MLP4 step: one
    op of each layer, one unplaced op and one the map lacks."""
    _, m = mlp_map
    pick = {}
    for name, layer in sorted(m.items()):
        pick.setdefault(layer, name)
    layers = sorted(k for k in pick if k is not None)
    ops, t = [], 0
    for layer in layers + [None]:
        ops.append((pick[layer], t, t + 10))
        t += 10
    ops.append(("not-in-the-step.1", t, t + 10))
    window = 10 * (len(ops) + 1)
    trace = trace_reduce.reduce_events(
        {0: ops}, [(trace_reduce.WINDOW_SPAN, 0, window)])
    run = _Run(trace, m)
    share = 100 * 10 / window
    local = sum(k.endswith("/local_loss") for k in layers)
    glue = sum(k.startswith("block") and not k.endswith("/local_loss")
               for k in layers)
    assert _reader("integer_sgd_update_share").read(run) == pytest.approx(
        share)
    assert _reader("local_loss_share").read(run) == pytest.approx(
        local * share)
    assert _reader("block_glue_share").read(run) == pytest.approx(
        glue * share)
    assert _reader("layer_unplaced_share").read(run) == pytest.approx(
        2 * share)


def test_no_map_for_a_cell_without_a_training_step(tiny_root):
    run = _tiny_run(tiny_root, "serve-vgg8b")
    assert layer_map.placed(run) is None


def test_traced_cpu_run_leaves_the_shares_out(tiny_root, capsys):
    """A CPU trace has no device plane: the four readers give nothing,
    and the run still ends with its line.  (The other readers of the cell
    need a chip's peaks, so only these four are asked for.)"""
    bench_file = tiny_root / "BENCHMARK.json"
    bench = json.loads(bench_file.read_text())
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] in READERS]
    assert len(bench["per_layer"]) == len(READERS)
    bench_file.write_text(json.dumps(bench))
    rc, line = run_cell(tiny_root, "train-mlp4", trace=1, capsys=capsys)
    assert rc == 0 and line["correct"] is True
    assert not set(READERS) & set(line["metrics"])
