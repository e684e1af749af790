#!/usr/bin/env python
"""Validate the machine-readable ``BENCH_*.json`` benchmark artifacts.

Every benchmark emits a JSON payload next to its stdout CSV; downstream
tooling (dashboards, regression diffs, the PR driver) reads those files
blind — so their shape is a contract.  This gate pins it:

  * **common**: an object with a non-empty ``benchmark`` string and a
    non-empty ``results`` list of objects;
  * **honesty invariant**: any ``bit_exact`` field must be ``true`` —
    a benchmark must never report timings for two computations that
    disagree.  (``meets_target`` is shape-checked but not value-checked:
    it reports a *timing* outcome, which machine contention can
    legitimately flip — a schema gate must stay deterministic);
  * **per-file**: the ``benchmark`` name matches the emitting module,
    ``BENCH_obs.json`` carries both overhead rows (train telemetry +
    fleet tracing), ``BENCH_serve.json`` carries the per-arm p99-vs-SLO
    roll-up with at least one configured SLO exercised, and
    ``BENCH_train.json`` carries the fused-opt rows: ``us_per_step``
    with both the ``fused_opt`` and ``unfused`` variants, the structural
    ``hbm_streams_per_weight_update`` counts (fused strictly fewer), and
    a ``fused_opt_no_worse_than_unfused`` bool (shape-checked only —
    a timing outcome, like ``meets_target``).

Usage (CI runs it after the benchmark smokes, from the repo root)::

    python tools/check_bench_schema.py            # all BENCH_*.json present
    python tools/check_bench_schema.py BENCH_obs.json   # specific files
"""

from __future__ import annotations

import glob
import json
import sys

#: file name → expected ``benchmark`` field of the emitting module.
EXPECTED_NAMES = {
    "BENCH_autotune.json": "autotune_gain",
    "BENCH_infer.json": "serve_infer",
    "BENCH_obs.json": "obs_overhead",
    "BENCH_parallel.json": "dp_scaling",
    "BENCH_serve.json": "serve_fleet",
    "BENCH_train.json": "train_step",
}


class SchemaError(Exception):
    pass


def _require(cond: bool, path: str, msg: str) -> None:
    if not cond:
        raise SchemaError(f"{path}: {msg}")


def _walk_honesty(path: str, node, where: str = "$") -> None:
    """``bit_exact`` must be True and ``meets_target`` a bool, anywhere."""
    if isinstance(node, dict):
        if "bit_exact" in node:
            _require(node["bit_exact"] is True, path,
                     f"{where}.bit_exact is {node['bit_exact']!r}, "
                     f"expected true")
        if "meets_target" in node:
            _require(isinstance(node["meets_target"], bool), path,
                     f"{where}.meets_target is not a bool")
        for k, v in node.items():
            _walk_honesty(path, v, f"{where}.{k}")
    elif isinstance(node, list):
        for i, v in enumerate(node):
            _walk_honesty(path, v, f"{where}[{i}]")


def _check_slo_block(path: str, slo: dict, where: str) -> None:
    _require(isinstance(slo.get("p99_ms"), (int, float)), path,
             f"{where}.p99_ms missing or non-numeric")
    if slo.get("slo_ms") is not None:
        for key in ("p99_slack_ms", "slo_violations", "violation_frac",
                    "meets_slo"):
            _require(key in slo, path, f"{where}.{key} missing (an arm "
                     f"with an SLO must report the full roll-up)")


def check_serve(path: str, payload: dict) -> None:
    slos_exercised = 0
    for i, result in enumerate(payload["results"]):
        runs = result.get("runs")
        _require(isinstance(runs, list) and runs, path,
                 f"results[{i}].runs missing or empty")
        for j, run in enumerate(runs):
            where = f"results[{i}].runs[{j}]"
            for key in ("scheduler", "requests", "latency_ms"):
                _require(key in run, path, f"{where}.{key} missing")
            if isinstance(run.get("slo"), dict):
                _check_slo_block(path, run["slo"], f"{where}.slo")
                slos_exercised += run["slo"].get("slo_ms") is not None
            for arm, slo in (run.get("arms") or {}).items():
                _check_slo_block(path, slo, f"{where}.arms[{arm}]")
                slos_exercised += slo.get("slo_ms") is not None
    _require(slos_exercised > 0, path,
             "no run exercised a configured SLO (every slo_ms is null)")


def check_obs(path: str, payload: dict) -> None:
    kinds = {r.get("kind") for r in payload["results"]}
    _require({"train_telemetry", "fleet_tracing"} <= kinds, path,
             f"expected both overhead rows, found kinds {sorted(kinds)}")
    for i, result in enumerate(payload["results"]):
        _require("meets_target" in result, path,
                 f"results[{i}].meets_target missing")


def check_autotune(path: str, payload: dict) -> None:
    """Tile-search results are *structurally* no-worse-than-default (the
    winner is the argmin of one paired session that includes the default),
    so that claim is value-checked; int8-vs-int32 outcomes are timing
    results and only shape-checked, like ``meets_target``."""
    for i, result in enumerate(payload["results"]):
        tiles = result.get("tiles")
        _require(isinstance(tiles, list) and tiles, path,
                 f"results[{i}].tiles missing or empty")
        for j, row in enumerate(tiles):
            where = f"results[{i}].tiles[{j}]"
            for key in ("op", "shape", "default_us", "tuned_us", "winner"):
                _require(key in row, path, f"{where}.{key} missing")
            _require(row.get("tuned_no_worse_than_default") is True, path,
                     f"{where}: tuned_us {row['tuned_us']} > default_us "
                     f"{row['default_us']} — the argmin must include the "
                     f"default probe")
        _require(result.get("tuned_no_worse_everywhere") is True, path,
                 f"results[{i}].tuned_no_worse_everywhere is not true")
        cache = result.get("cache")
        _require(isinstance(cache, dict), path, f"results[{i}].cache missing")
        _require(cache.get("second_resolution_measurement_free") is True,
                 path, f"results[{i}]: a warm cache must resolve every "
                 f"tuned problem measurement-free")
        _require(cache.get("second_resolution_hits") == len(tiles), path,
                 f"results[{i}]: {cache.get('second_resolution_hits')} "
                 f"cache hits != {len(tiles)} tuned problems")
        int8 = result.get("int8_layers")
        _require(isinstance(int8, list), path,
                 f"results[{i}].int8_layers missing")
        _require(len(int8) == result.get("int8_eligible_steps"), path,
                 f"results[{i}]: {len(int8)} int8 rows != "
                 f"{result.get('int8_eligible_steps')} eligible steps")
        for j, row in enumerate(int8):
            where = f"results[{i}].int8_layers[{j}]"
            for key in ("int8_us", "int32_us", "alpha_inv"):
                _require(isinstance(row.get(key), (int, float)), path,
                         f"{where}.{key} missing or non-numeric")
            _require(isinstance(row.get("int8_wins"), bool), path,
                     f"{where}.int8_wins is not a bool")


def check_train(path: str, payload: dict) -> None:
    """The fused-IntegerSGD rows: timings for both the fused-opt and the
    split-update step, the structural HBM-stream counts (a claim about
    the kernel dataflow, so value-checked: fused must stream strictly
    less), and the no-worse bool (a timing outcome — shape-checked
    only)."""
    for i, result in enumerate(payload["results"]):
        where = f"results[{i}]"
        us = result.get("us_per_step")
        _require(isinstance(us, dict), path, f"{where}.us_per_step missing")
        for variant in ("fused_opt", "unfused"):
            _require(isinstance(us.get(variant), (int, float)), path,
                     f"{where}.us_per_step[{variant!r}] missing or "
                     f"non-numeric")
        streams = result.get("hbm_streams_per_weight_update")
        _require(isinstance(streams, dict), path,
                 f"{where}.hbm_streams_per_weight_update missing")
        for key in ("fused_opt", "unfused_opt"):
            _require(isinstance(streams.get(key), int), path,
                     f"{where}.hbm_streams_per_weight_update[{key!r}] "
                     f"missing or non-integer")
        _require(streams["fused_opt"] < streams["unfused_opt"], path,
                 f"{where}: fused_opt streams {streams['fused_opt']} not "
                 f"< unfused_opt streams {streams['unfused_opt']} — the "
                 f"epilogue exists to remove the grad_W round-trip")
        _require(isinstance(result.get("fused_opt_no_worse_than_unfused"),
                            bool), path,
                 f"{where}.fused_opt_no_worse_than_unfused is not a bool")


def check_file(path: str) -> None:
    with open(path) as f:
        payload = json.load(f)
    _require(isinstance(payload, dict), path, "top level is not an object")
    name = payload.get("benchmark")
    _require(isinstance(name, str) and name, path,
             "missing non-empty 'benchmark' string")
    expected = EXPECTED_NAMES.get(path.rsplit("/", 1)[-1])
    if expected is not None:
        _require(name == expected, path,
                 f"benchmark {name!r} != expected {expected!r}")
    results = payload.get("results")
    _require(isinstance(results, list) and results, path,
             "missing non-empty 'results' list")
    _require(all(isinstance(r, dict) for r in results), path,
             "every results[] entry must be an object")
    _walk_honesty(path, payload)
    if name == "serve_fleet":
        check_serve(path, payload)
    elif name == "obs_overhead":
        check_obs(path, payload)
    elif name == "autotune_gain":
        check_autotune(path, payload)
    elif name == "train_step":
        check_train(path, payload)


def main(argv: list[str]) -> int:
    paths = argv or sorted(glob.glob("BENCH_*.json"))
    if not paths:
        print("check_bench_schema: no BENCH_*.json found "
              "(run the benchmarks first)", file=sys.stderr)
        return 1
    failures = 0
    for path in paths:
        try:
            check_file(path)
        except (OSError, json.JSONDecodeError, SchemaError) as e:
            print(f"check_bench_schema: FAIL {e}", file=sys.stderr)
            failures += 1
        else:
            print(f"check_bench_schema: ok {path}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
