"""Tiny cells for the CPU tests: the benchmark's jobs driven end to end at a
size a test run holds, with the harness's look for a chip skipped.

The tiny sizes are data, found by name: a configuration's in
``tiny_sizes/configs/<config>.json``, a job's in ``tiny_sizes/jobs/<job>.json``.
A cell is a workload of ``BENCHMARK.json`` or a (configuration, traffic)
pair, for a committed mix that is not a cell."""
from __future__ import annotations

import time
from pathlib import Path
from typing import Union

from bench import harness


def _sizes(root: Path, kind: str, name: str) -> dict:
    return harness.read_json(root / "bench" / "tests" / "tiny_sizes" / kind
                             / f"{name}.json")


def tiny_config(name: str, root: Path = harness.ROOT) -> dict:
    """A configuration as committed, at its tiny sizes."""
    return {**harness.read_json(root / "bench" / "configs" / f"{name}.json"),
            **_sizes(root, "configs", name)}


def tiny_cell(cell: Union[str, tuple[str, str]]) -> harness.Cell:
    """A configuration under a traffic mix, as committed, at tiny widths
    and loads (every other parameter, limits included, as committed)."""
    if isinstance(cell, str):
        w = next(w for w in harness.read_json(
            harness.ROOT / "BENCHMARK.json")["workloads"] if w["name"] == cell)
        name, config_name, traffic = cell, w["config"], w["traffic"]
    else:
        (config_name, traffic), name = cell, ".".join(cell)
    mix = harness.read_json(harness.ROOT / "bench" / "traffic"
                            / f"{traffic}.json")
    mix.update(_sizes(harness.ROOT, "jobs", mix["job"]))
    return harness.Cell(name=name, chips=1, config=tiny_config(config_name),
                        mix=mix, end_to_end=[], per_layer=[])


def run_tiny(cell: Union[str, tuple[str, str]], seed: int = 3,
             seconds: float = 1.0, control=None) -> harness.JobResult:
    from bench.run import JobContext
    cell = tiny_cell(cell)
    ctx = JobContext(cell=cell, seed=seed, seconds=seconds, trace=False,
                     clock=harness.CompileClock(),
                     t_start=time.perf_counter(), control=control)
    return harness.job_module(cell.job).run(ctx)
