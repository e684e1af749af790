"""Each device op of a traced training window, placed on a layer of the
LES step.

The program opens a ``jax.named_scope`` around each part of the step
(``src/repro/obs/layers.py``).  XLA keeps the scope in the ``op_name``
metadata of every instruction it makes from the ops inside, fusions
included, and a profiler trace names each device op by its instruction
name (``fusion.18``).  So the compiled step's text maps op names to
layers:

* ``block{i}/forward``, ``block{i}/local_loss``, ``block{i}/backward``;
* ``output``, ``update`` and ``reduce_gradients`` (the program's
  ``dp/reduce_gradients``).

An instruction whose path holds several of these scopes is placed on the
innermost.  An op that the text does not hold, or whose path holds none
of them (an XLA-inserted copy with no metadata), is unplaced.  The scope
strings are fixed here, as ``^stream_conv`` is in its reader, so that no
change of the program moves the yardstick.

``placed(run)`` builds the map once per run, after the window: it
compiles the cell's step exactly as the generator's ``setup`` does, on
inputs made from the same seed, which the persistent compilation cache
turns into a load of the executable that ran.  It gives None where the
cell has no training step to compile, or where the compiled step names
none of the LES step's layers (a program older than its scopes).

    python bench/layer_map.py --workload <cell> --seed <n> [--seconds <s>]

runs one traced window of the cell on the chip and prints its result
line and the device seconds of every layer, split into the Pallas
kernels and the rest.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import time
from collections import defaultdict

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness, model  # noqa: E402

OUTPUT = "output"
UPDATE = "update"
REDUCE_GRADIENTS = "reduce_gradients"
BLOCK_PARTS = ("forward", "local_loss", "backward")
#: a block's scope in an ``op_name`` path: ``.../block3/backward/...``
BLOCK_SCOPE = re.compile(rf"(?:^|/)block(\d+)/({'|'.join(BLOCK_PARTS)})"
                         r"(?=/|$)")
#: the step's other scopes, by the layer each names
NAMED_LAYER = {"output": OUTPUT, "update": UPDATE,
               "dp/reduce_gradients": REDUCE_GRADIENTS}
NAMED_SCOPE = re.compile(rf"(?:^|/)({'|'.join(NAMED_LAYER)})(?=/|$)")
#: the Pallas kernels, by the names of their calls
KERNELS = re.compile(r"^(stream_conv|nitro_matmul|integer_sgd)")
#: an instruction of HLO text, ``%name = type op(...), metadata={...}``
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')

_CACHE_ATTR = "layer_map"


def layer_of(path: str) -> str | None:
    """The layer an ``op_name`` path lies on (the innermost scope), or
    None."""
    found = [(m.start(), f"block{m.group(1)}/{m.group(2)}")
             for m in BLOCK_SCOPE.finditer(path)]
    found += [(m.start(), NAMED_LAYER[m.group(1)])
              for m in NAMED_SCOPE.finditer(path)]
    return max(found)[1] if found else None


def parse_hlo(text: str) -> dict:
    """``{instruction name: layer or None}`` for every instruction of an
    HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            path = OP_NAME.search(line)
            out[m.group(1)] = layer_of(path.group(1)) if path else None
    return out


def names_the_step(mapping: dict) -> bool:
    """Whether a map places anything on the LES step's own layers (the
    exchange's scope alone is older than them)."""
    return any(v is not None and v != REDUCE_GRADIENTS
               for v in mapping.values())


def compiled_step_text(run) -> str | None:
    """HLO text of the cell's compiled training step, or None where the
    cell's generator builds none."""
    from bench.traffic import train_closed

    gen = harness.generator(run.root, run.mix["kind"])
    if not hasattr(gen, "build_step"):
        return None
    k_weights, xs, ys, keys = train_closed.make_inputs(run)
    state = model.train_state(run.config,
                              model.init_params(run.config, k_weights))
    step = gen.build_step(run, run.config)
    compiled = step.lower(state, x=xs[0], labels=ys[0], key=keys[0]).compile()
    return compiled.as_text()


def build(run) -> dict | None:
    t = time.perf_counter()
    text = compiled_step_text(run)
    mapping = parse_hlo(text) if text is not None else {}
    placed_n = sum(v is not None for v in mapping.values())
    print(f"layer map: {placed_n} of {len(mapping)} instructions placed, "
          f"built in {time.perf_counter() - t:.3f} s",
          file=sys.stderr, flush=True)
    return mapping if names_the_step(mapping) else None


def placed(run) -> dict | None:
    """The run's ``{op name: layer or None}``, built once (see ``build``)."""
    if not hasattr(run, _CACHE_ATTR):
        setattr(run, _CACHE_ATTR, build(run))
    return getattr(run, _CACHE_ATTR)


def is_block_glue(layer, op: str) -> bool:
    return (layer is not None and layer.startswith("block")
            and not layer.endswith("/local_loss") and not KERNELS.match(op))


def share(run, keep) -> float | None:
    """Summed device time of the ops for which ``keep(layer, op name)``
    holds, averaged over the chips, in % of the traced window; None where
    the trace has no ops or the map cannot be built."""
    t = run.reduced_trace
    if t is None or not any(t.ops.values()):
        return None
    mapping = placed(run)
    if mapping is None:
        return None
    ns = sum(e - s for ops in t.ops.values() for n, s, e in ops
             if keep(mapping.get(n), n))
    return 100.0 * ns / 1e9 / t.chips / t.window_s


def layer_seconds(trace, mapping: dict) -> dict:
    """``{layer: {"kernels": s, "other": s}}`` of device time, averaged
    over the chips; unplaced ops under ``"unplaced"``."""
    out = defaultdict(lambda: {"kernels": 0.0, "other": 0.0})
    for ops in trace.ops.values():
        for n, s, e in ops:
            part = "kernels" if KERNELS.match(n) else "other"
            out[mapping.get(n) or "unplaced"][part] += (e - s) / 1e9
    return {k: {p: v / trace.chips for p, v in d.items()}
            for k, d in sorted(out.items())}


def main(argv=None) -> int:
    """One traced run of a cell, as ``bench/run.py --trace 1`` makes it;
    prints its result line, the seconds the map took to build, and the
    device seconds of every layer."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=harness.TRACE_SECONDS)
    args = ap.parse_args(argv)
    root = harness.DEFAULT_ROOT
    bench, workload, config, mix = harness.find_cell(root, args.workload)
    run = harness.Run(root=root, bench=bench, workload=workload,
                      config=config, mix=mix, seed=args.seed,
                      seconds=args.seconds, trace=True,
                      t0=time.perf_counter())
    run.devices = harness.accelerator(run.chips)
    harness.set_compile_cache(root)
    sys.path.insert(0, str(root / "src"))
    checks = harness.measure(run, harness.generator(root, mix["kind"]))
    t = time.perf_counter()
    mapping = placed(run)
    build_s = time.perf_counter() - t
    line = harness.result_line(run, checks)
    layers = layer_seconds(run.reduced_trace, mapping or {})
    print(json.dumps({"line": line, "map_build_s": build_s,
                      "layers": layers}), flush=True)
    return 0 if mapping is not None else 1


if __name__ == "__main__":
    sys.exit(main())
