"""What every job of the chip benchmark shares.

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell's ``config`` and ``traffic``; the
configuration is ``bench/configs/<config>.json``, the mix
``bench/traffic/<traffic>.json``, and the mix names its job,
``bench/jobs/<job>.py``.  A per-layer metric is ``bench/metrics/<name>.py``.
The configuration's block family (``program.family``, default ``dense``)
names its plain reference, ``bench/reference/<family>.py``, and its work
counts, ``bench/geometry/<family>.py`` (see ``family_module``).

A new configuration brings, as new files:

- ``bench/configs/<config>.json``: the published ``config.json`` keys
  (``arch_config`` reads those it knows), ``name``, ``source``, and a
  ``program`` block for what else the program's ``ArchConfig`` needs, by
  field name (such as the experts one chip holds);
- where its family is new, ``bench/reference/<family>.py`` (``Arch``,
  ``class_loss``, ``lm_logits``, ``segments``) and
  ``bench/geometry/<family>.py`` (``Geometry``, as ``bench/counts.py``
  reads it);
- ``bench/tests/tiny_sizes/configs/<config>.json``: the sizes its CPU
  tests run at;

and, as entries in ``BENCHMARK.json``, its cell in ``workloads`` and the
cell's name in the ``workloads`` list of every metric its job reports.
Nothing here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
import sys
import time
from dataclasses import MISSING, dataclass, field, fields
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints none."""

    def __init__(self, what: str):
        super().__init__(f"bench: {what}")


class WindowClosed(Exception):
    """Raised from a hook inside the program's own loop when the measured
    window has ended: the loop stops where it stands, with no drain."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def _shown(path: Path) -> str:
    try:
        return str(path.relative_to(ROOT))
    except ValueError:
        return str(path)


def read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing {_shown(path)}") from None


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {_shown(path)}")
    name = (f"bench_{path.parent.name}_"
            + path.stem.replace(".", "_").replace("-", "_"))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # where a dataclass looks up its module
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list

    @property
    def job(self) -> str:
        return self.mix["job"]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    config = read_json(root / "bench" / "configs" / f"{w['config']}.json")
    mix = read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if applies(m, name)])


def job_module(job: str, root: Path = ROOT) -> ModuleType:
    return load_module(root / "bench" / "jobs" / f"{job}.py")


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


def family_module(config: dict, kind: str, root: Path = ROOT) -> ModuleType:
    """``bench/<kind>/<family>.py`` of the configuration's block family:
    ``kind`` is ``reference`` or ``geometry``."""
    family = config.get("program", {}).get("family", "dense")
    return load_module(root / "bench" / kind / f"{family}.py")


def segment_slices(segments: list) -> dict[str, slice]:
    """Each stacked segment's rows as a slice of the layer (mask) index:
    ``segments`` is the family's ``[(name, layers), ...]`` in layer order."""
    out, off = {}, 0
    for name, n in segments:
        out[name] = slice(off, off + int(n))
        off += int(n)
    return out


def split_layers(segments: list, layers) -> dict:
    """Layer indices (a user's delta layers) by the segment that holds
    them: ``{name: (positions in layers, rows of the segment)}``."""
    import numpy as np
    layers = np.asarray(layers)
    out = {}
    for name, sl in segment_slices(segments).items():
        pos = np.flatnonzero((layers >= sl.start) & (layers < sl.stop))
        if pos.size:
            out[name] = (pos, layers[pos] - sl.start)
    return out


# ---------------------------------------------------------------------------
# what a job hands back
# ---------------------------------------------------------------------------

@dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return not math.isnan(self.value) and self.value <= self.limit


@dataclass
class JobResult:
    setup_s: float
    metrics: dict[str, float]            # end-to-end, by name
    attempted: int
    failed: int
    checks: list[Check]
    peak_bytes: Optional[int]
    compiles_in_window: int
    # what the per-layer readers read: work counts of the window, trace
    context: dict[str, Any] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


# ---------------------------------------------------------------------------
# device and compile bookkeeping
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and ``programs``:
    how many times it traced a function or compiled a program, whether or
    not the persistent cache then held the executable.  A window that only
    reuses what set-up built adds none."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.programs += event != _COMPILE_EVENTS[1]


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where it
    is set, else ``.jax_cache/`` at the checkout's root, a fixed path.
    Every program is cached, however quick its compile."""
    import os

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def device_barrier() -> None:
    """Wait until the device has run everything dispatched before: a tiny
    program queued behind them, and waited for."""
    import jax
    import jax.numpy as jnp
    jax.block_until_ready(_barrier_fn()(jnp.zeros((), jnp.float32)))


@functools.cache
def _barrier_fn():
    import jax
    return jax.jit(lambda x: x + 1)


def now() -> float:
    return time.perf_counter()


# ---------------------------------------------------------------------------
# the program's configuration from a benchmark configuration file
# ---------------------------------------------------------------------------

_ACTS = {"gelu": "gelu_plain", "silu": "silu"}


def _act(name: str) -> str:
    if name not in _ACTS:
        raise BenchError(f"hidden_act {name!r} is none the program has "
                         f"(have {sorted(_ACTS)})")
    return _ACTS[name]


# ArchConfig field <- the published config.json keys that mean the same
# thing (the first one present wins), and the field's type.
_KEYS = (
    ("n_layers", ("num_hidden_layers",), int),
    ("d_model", ("hidden_size",), int),
    ("n_heads", ("num_attention_heads",), int),
    ("n_kv_heads", ("num_key_value_heads",), int),
    ("head_dim", ("head_dim",), int),
    ("d_ff", ("intermediate_size",), int),
    ("vocab_size", ("vocab_size",), int),
    ("mlp_act", ("hidden_act",), _act),
    ("rope_theta", ("rope_theta",), float),
    ("norm_eps", ("rms_norm_eps", "layer_norm_eps"), float),
    ("tie_embeddings", ("tie_word_embeddings",), bool),
    ("dtype", ("torch_dtype",), str),
    ("n_experts", ("n_routed_experts", "num_local_experts", "num_experts"),
     int),
    ("n_shared_experts", ("n_shared_experts",), int),
    ("top_k", ("num_experts_per_tok",), int),
    ("first_dense", ("first_k_dense_replace",), int),
    ("kv_lora_rank", ("kv_lora_rank",), int),
    ("qk_rope_dim", ("qk_rope_head_dim",), int),
    ("qk_nope_dim", ("qk_nope_head_dim",), int),
    ("v_head_dim", ("v_head_dim",), int),
    ("ssm_state", ("state_size", "mamba_d_state"), int),
    ("ssm_expand", ("expand", "mamba_expand"), int),
    ("ssm_groups", ("n_groups", "mamba_ngroups"), int),
    ("ssm_conv", ("conv_kernel", "mamba_d_conv"), int),
    ("ssm_chunk", ("chunk_size",), int),
    ("sliding_window", ("sliding_window",), int),
)
# what a published file that leaves the key out means: no rotary positions
# (the program's sinusoidal ones), an untied head, the dense family
_ABSENT = {"family": "dense", "rope_theta": 0.0, "tie_embeddings": False}


def arch_config(config: dict):
    """``repro.configs.base.ArchConfig`` for a configuration file: the
    published ``config.json`` keys through ``_KEYS`` (a key's absence read
    as the published convention: as many key/value heads as heads, a head
    ``hidden_size / num_attention_heads`` wide), then the ``program``
    block, which sets any field by its name and wins over the table."""
    from repro.configs.base import ArchConfig
    prog = config.get("program", {})
    unknown = sorted(set(prog) - {f.name for f in fields(ArchConfig)})
    if unknown:
        raise BenchError(f"program keys {unknown} name no ArchConfig field")
    got = {"name": config["name"], "source": config["source"], **_ABSENT}
    for name, keys, kind in _KEYS:
        key = next((k for k in keys if config.get(k) is not None), None)
        if key is not None:
            got[name] = kind(config[key])
    if "n_heads" in got:
        got.setdefault("n_kv_heads", got["n_heads"])
        if "d_model" in got:
            got.setdefault("head_dim", got["d_model"] // got["n_heads"])
    got.update(prog)
    missing = sorted(f.name for f in fields(ArchConfig)
                     if f.default is MISSING and f.name not in got)
    if missing:
        raise BenchError(f"{config['name']}: no value for {missing}; give "
                         f"them in the configuration's program block")
    return ArchConfig(**got)


def program_seed(seed: int) -> int:
    """The seed as the program's numpy and JAX generators take it."""
    return int(seed) % (2 ** 31 - 1)
