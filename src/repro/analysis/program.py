"""Program auditor: enumerate → lower → extract facts → gate (DESIGN.md §11).

The source linter (repro.analysis.engine) checks what the *code* says; this
module checks what the *compiled programs* do.  It enumerates every program
family the jit-suite cache can hold — the dense round step, all ≤L+1
masked-cut variants, the probe, the fused probe+update, the serve decode
programs (shared / delta / dense baseline) and the donated delta/bank
writes — lowers each on shape-only abstract inputs (nothing executes), and
extracts a :class:`repro.analysis.facts.ProgramFacts` row per program.

Two gates read the fact table:

* :mod:`repro.analysis.contracts` — version-robust invariants (FLOPs
  monotone in the cut, B-independent delta weight traffic, donation
  honored, dtype discipline, collective/transfer allowlist).
* the budget manifest ``experiments/bench/PROGRAM_BUDGETS.json`` — absolute
  per-program FLOPs/bytes/memory with per-metric tolerances, refreshed via
  ``python -m repro.analysis program --update-budgets`` and diffed in CI by
  the program-audit job and ``benchmarks/micro_ci.py``.

Audit configs are tiny ``reduced()`` variants (dense tinyllama + ssm
mamba2, plus a bf16 dense variant for the serve dtype contract) chosen so
block FLOPs dominate the loss head — the roofline crosscheck in
tests/test_hlo_cost.py depends on that.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..", "..", "..")
DEFAULT_BUDGETS_PATH = os.path.normpath(os.path.join(
    REPO_ROOT, "experiments", "bench", "PROGRAM_BUDGETS.json"))

# Relative drift allowed per budget metric before the gate fails.  flops is
# the tight one (it is what the contracts reason about); the byte/memory
# models absorb more XLA-version noise (fusion decisions move fusion-
# boundary traffic and temp sizes without changing the program's math).
BUDGET_TOLERANCES = {
    "flops": 0.10,
    "hbm_bytes": 0.35,
    "weight_bytes": 0.10,
    "arg_bytes": 0.25,
    "temp_bytes": 0.60,
}
BUDGET_KEYS = tuple(BUDGET_TOLERANCES)


@dataclass
class ProgramSpec:
    """One auditable program: a jitted fn + its abstract inputs."""
    name: str
    fn: Callable
    args: tuple
    static_argnums: tuple = ()
    donate_argnums: tuple = ()
    weight_argnums: tuple = ()
    meta: dict = field(default_factory=dict)


def audit_models() -> list[tuple[str, Any, dict]]:
    """(label, Model, {train: bool, serve: bool}) triples for the audit.

    ``remat=False`` keeps the trained-layer cost at the paper's 3× forward
    (1 fwd + 2 bwd) — the ratio benchmarks/roofline.py's speedup model and
    the cut-monotonicity margins assume.
    """
    import dataclasses

    from repro.configs.base import RuntimeConfig, get_arch, reduced
    from repro.models.model import Model

    rt = RuntimeConfig(remat=False, seq_chunk=32)
    dense = reduced(get_arch("tinyllama_1_1b"), n_layers=4, d_model=64)
    ssm = reduced(get_arch("mamba2_370m"), n_layers=4, d_model=64)
    bf16 = dataclasses.replace(dense, dtype="bfloat16")
    return [
        ("dense", Model(dense, rt), {"train": True, "serve": True}),
        ("ssm", Model(ssm, rt), {"train": True, "serve": False}),
        ("dense_bf16", Model(bf16, rt), {"train": False, "serve": True}),
    ]


def enumerate_specs(models: Optional[list] = None) -> list[ProgramSpec]:
    """Every audited program across the audit configs, name-prefixed by
    config label (``dense/fl_step_masked/cut2``, ...)."""
    from repro.core.client import suite_program_specs
    from repro.serve.engine import serve_program_specs

    specs: list[ProgramSpec] = []
    for label, model, what in (models if models is not None
                               else audit_models()):
        rows: list[dict] = []
        if what.get("train"):
            rows += suite_program_specs(model)
        if what.get("serve"):
            rows += serve_program_specs(model)
        for r in rows:
            meta = dict(r["meta"], config=label)
            specs.append(ProgramSpec(
                name=f"{label}/{r['name']}", fn=r["fn"], args=tuple(r["args"]),
                static_argnums=tuple(r["static_argnums"]),
                donate_argnums=tuple(r["donate_argnums"]),
                weight_argnums=tuple(r["weight_argnums"]), meta=meta))
    return specs


def run_audit(specs: Optional[Sequence[ProgramSpec]] = None,
              progress: Optional[Callable[[str], None]] = None) -> dict:
    """Lower + extract facts for every spec.  Returns {name: ProgramFacts}."""
    from repro.analysis.facts import extract_facts

    if specs is None:
        specs = enumerate_specs()
    facts = {}
    for s in specs:
        if progress:
            progress(s.name)
        facts[s.name] = extract_facts(
            s.name, s.fn, s.args, static_argnums=s.static_argnums,
            donate_argnums=s.donate_argnums, weight_argnums=s.weight_argnums,
            meta=s.meta)
    return facts


# -- budget manifest ---------------------------------------------------------

def budgets_from_facts(facts: dict) -> dict:
    import jax
    return {
        "_meta": {
            "tolerances": BUDGET_TOLERANCES,
            "jax_version": jax.__version__,
            "refresh": "PYTHONPATH=src python -m repro.analysis program"
                       " --update-budgets",
        },
        "programs": {
            name: {k: getattr(f, k) for k in BUDGET_KEYS}
            for name, f in sorted(facts.items())},
    }


def load_budgets(path: str = DEFAULT_BUDGETS_PATH) -> Optional[dict]:
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        return json.load(fh)


def save_budgets(facts: dict, path: str = DEFAULT_BUDGETS_PATH) -> dict:
    manifest = budgets_from_facts(facts)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(manifest, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return manifest


def check_budgets(facts: dict, manifest: dict) -> list[str]:
    """Diff audited facts against the committed manifest.

    New/vanished programs are drift too: a program silently falling out of
    the audit is exactly the kind of regression the gate exists to catch.
    """
    failures: list[str] = []
    tols = dict(BUDGET_TOLERANCES,
                **manifest.get("_meta", {}).get("tolerances", {}))
    committed = manifest.get("programs", {})
    for name in sorted(set(facts) - set(committed)):
        failures.append(f"{name}: audited but missing from manifest "
                        f"(new program? run --update-budgets)")
    for name in sorted(set(committed) - set(facts)):
        failures.append(f"{name}: in manifest but no longer audited "
                        f"(vanished program? run --update-budgets)")
    for name in sorted(set(facts) & set(committed)):
        f = facts[name]
        for key, want in committed[name].items():
            have = getattr(f, key, None)
            if have is None:
                continue
            tol = tols.get(key, 0.25)
            base = max(abs(want), 1.0)
            drift = abs(have - want) / base
            if drift > tol:
                failures.append(
                    f"{name}: {key} drifted {drift:+.1%} beyond ±{tol:.0%} "
                    f"(budget {want:.3g}, audited {have:.3g})")
    return failures


def audit_report(facts: dict, violations, budget_failures) -> dict:
    """The machine-readable report ``python -m repro.analysis program
    --json`` emits (and the CI annotation step consumes)."""
    return {
        "programs": {n: f.to_dict() for n, f in sorted(facts.items())},
        "violations": [v.to_dict() for v in violations],
        "budget_failures": list(budget_failures),
        "ok": not violations and not budget_failures,
    }
