"""Program auditor (DESIGN.md §11): the repo's programs satisfy every
contract, the committed budget manifest matches a fresh audit, and —
the other half of the acceptance bar — every contract FAILS when its
invariant is deliberately broken (tripwire injections: an f64 cast, a
dropped donation, batch-dependent delta traffic, non-monotone cuts,
smuggled collectives/transfers, a budget drift)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import program as P
from repro.analysis.contracts import (check_all, check_cut_monotone,
                                      check_delta_traffic, check_donation,
                                      check_dtypes, check_isolation)
from repro.analysis.facts import ProgramFacts, extract_facts, weight_traffic

SDS = jax.ShapeDtypeStruct


def by_kind(facts, kind, config=None):
    return {f.meta["cut"] if kind == "fl_step_masked" else f.name: f
            for f in facts.values()
            if f.meta.get("kind") == kind
            and (config is None or f.meta.get("config") == config)}


# -- the repo's programs pass every contract ---------------------------------

def test_subset_contracts_clean(program_audit_facts):
    violations = check_all(program_audit_facts)
    assert not violations, "\n".join(
        f"{v.contract} {v.program}: {v.message}" for v in violations)


def test_masked_cut_flops_strictly_decreasing(program_audit_facts):
    for cfg in ("dense", "ssm"):
        cuts = by_kind(program_audit_facts, "fl_step_masked", cfg)
        assert len(cuts) >= 3
        series = [cuts[c].flops for c in sorted(cuts)]
        assert all(b < a for a, b in zip(series, series[1:])), (cfg, series)
        # deepest cut is forward-only: well under half the full train step
        assert series[-1] < 0.5 * series[0]


def test_delta_weight_traffic_b_independent(program_audit_facts):
    rows = [f for f in program_audit_facts.values()
            if f.meta.get("kind") == "serve_decode_delta"]
    caps = sorted({f.meta["capacity"] for f in rows})
    for C in caps:
        w = {f.meta["batch"]: f.weight_bytes for f in rows
             if f.meta["capacity"] == C}
        assert len(w) == 2
        b_lo, b_hi = sorted(w)
        # exact in the jaxpr model: the overlay streams (1+C) rows per
        # layer no matter how many slots decode in the batch
        assert w[b_lo] == pytest.approx(w[b_hi], rel=1e-6), (C, w)
    # the contrast that makes it meaningful: dense per-user params scale
    dense = {f.meta["batch"]: f.weight_bytes
             for f in program_audit_facts.values()
             if f.meta.get("kind") == "serve_decode_dense"}
    b_lo, b_hi = sorted(dense)
    assert dense[b_hi] == pytest.approx(dense[b_lo] * b_hi / b_lo, rel=1e-6)


# -- tripwires: each contract must fail when its invariant is broken ---------

def masked_fact(name, cut, flops, L=4, config="t"):
    return ProgramFacts(name=name, flops=flops,
                        meta={"kind": "fl_step_masked", "cut": cut,
                              "n_selectable": L, "config": config,
                              "single_host": True})


def test_cut_monotone_tripwire_non_decreasing():
    facts = {f.name: f for f in [
        masked_fact("t/cut0", 0, 100.0), masked_fact("t/cut1", 1, 80.0),
        masked_fact("t/cut2", 2, 85.0)]}
    out = check_cut_monotone(facts)
    assert [v.contract for v in out] == ["cut-monotone"]
    assert "not strictly decreasing" in out[0].message


def test_cut_monotone_tripwire_backward_not_elided():
    # monotone, but cut=L still costs 65% of cut=0: backward survived
    flops = [100.0, 90.0, 80.0, 70.0, 65.0]
    facts = {f.name: f for f in [
        masked_fact(f"t/cut{c}", c, fl) for c, fl in enumerate(flops)]}
    out = check_cut_monotone(facts)
    assert len(out) == 1 and "forward-only" in out[0].message


def test_delta_traffic_tripwire_b_dependence(program_audit_facts):
    facts = {n: f for n, f in program_audit_facts.items()
             if f.meta.get("kind") in ("serve_decode_delta",
                                       "serve_decode_dense")}
    name = "dense/serve_decode_delta/B6/C1"
    facts[name] = dataclasses.replace(
        facts[name], weight_bytes=facts[name].weight_bytes * 2)
    out = check_delta_traffic(facts)
    assert any(v.program == name and "depend on batch" in v.message
               for v in out)


def test_delta_traffic_tripwire_dense_stops_scaling(program_audit_facts):
    facts = {n: f for n, f in program_audit_facts.items()
             if f.meta.get("kind") in ("serve_decode_delta",
                                       "serve_decode_dense")}
    lo, hi = "dense/serve_decode_dense/B3", "dense/serve_decode_dense/B6"
    facts[hi] = dataclasses.replace(
        facts[hi], weight_bytes=facts[lo].weight_bytes)
    out = check_delta_traffic(facts)
    assert any(v.program == hi and "should scale" in v.message for v in out)


def test_donation_tripwire():
    """Declared-donated but not jit-donated: XLA applies no alias and the
    donation-honored contract must fire; the genuinely donated twin must
    pass."""
    tree = {k: SDS((16, 16), jnp.float32) for k in ("a", "b")}

    def bump(t):
        return {k: v + 1.0 for k, v in t.items()}

    bad = extract_facts("t/bad", jax.jit(bump), (tree,), donate_argnums=(0,))
    assert bad.donated_declared == 2 and bad.donation_applied == 0
    out = check_donation({"t/bad": bad})
    assert [v.contract for v in out] == ["donation-honored"]

    good = extract_facts("t/good", jax.jit(bump, donate_argnums=0), (tree,),
                         donate_argnums=(0,))
    assert good.donation_applied >= good.donated_declared == 2
    assert not check_donation({"t/good": good})


def test_f64_tripwire():
    """An injected double-precision cast must trip dtype-discipline even
    though the program's outputs are f32 again."""
    def leak(x):
        return (x.astype(jnp.float64) * 2.0).sum().astype(jnp.float32)

    with jax.enable_x64(True):
        f = extract_facts("t/f64", jax.jit(leak), (SDS((8,), jnp.float32),))
    assert "float64" in f.jaxpr_dtypes
    out = check_dtypes({"t/f64": f})
    assert [v.contract for v in out] == ["dtype-discipline"]


def test_bf16_leak_tripwire(program_audit_facts):
    real = program_audit_facts["dense_bf16/serve_decode/B3"]
    assert "bfloat16" in real.out_dtypes          # the passing repo check
    leaky = ProgramFacts(
        name="t/bf16", meta=dict(real.meta),
        out_dtypes=["float32"] * len(real.out_dtypes))
    out = check_dtypes({"t/bf16": leaky})
    assert [v.contract for v in out] == ["dtype-discipline"]
    assert "leaks f32" in out[0].message


def test_isolation_tripwires(program_audit_facts):
    base = program_audit_facts["dense/serve_decode_dense/B3"]
    assert base.meta["single_host"] and not base.collective_counts

    smuggled = dataclasses.replace(
        base, collective_counts={"all-reduce": 2})
    leaking = dataclasses.replace(base, transfer_ops={"outfeed": 1})
    out = check_isolation({"t/coll": smuggled, "t/xfer": leaking})
    assert {v.contract for v in out} == {"collective-transfer-allowlist"}
    assert len(out) == 2

    # sharded programs: only mesh-declared collective kinds pass
    sharded_meta = dict(base.meta, single_host=False,
                        allowed_collectives=("all-reduce",))
    ok = dataclasses.replace(base, meta=sharded_meta,
                             collective_counts={"all-reduce": 4})
    rogue = dataclasses.replace(base, meta=sharded_meta,
                                collective_counts={"all-gather": 1})
    out = check_isolation({"t/ok": ok, "t/rogue": rogue})
    assert len(out) == 1 and "all-gather" in out[0].message


# -- budget manifest ----------------------------------------------------------

def test_committed_budgets_match_audit(program_audit_facts):
    """The committed PROGRAM_BUDGETS.json is fresh: a re-audit of the
    subset lands inside every per-metric tolerance."""
    manifest = P.load_budgets()
    assert manifest is not None, "PROGRAM_BUDGETS.json missing — run " \
        "`python -m repro.analysis program --update-budgets`"
    sub = {"_meta": manifest["_meta"],
           "programs": {n: manifest["programs"][n]
                        for n in program_audit_facts}}
    assert len(sub["programs"]) == len(program_audit_facts)
    failures = P.check_budgets(program_audit_facts, sub)
    assert not failures, "\n".join(failures)


def test_budget_drift_detected(program_audit_facts):
    manifest = P.budgets_from_facts(program_audit_facts)
    name = "dense/fl_step_masked/cut0"
    manifest["programs"][name]["flops"] *= 1.5     # way past the 10% band
    failures = P.check_budgets(program_audit_facts, manifest)
    assert any(name in m and "flops drifted" in m for m in failures)


def test_budget_membership_is_drift(program_audit_facts):
    manifest = P.budgets_from_facts(program_audit_facts)
    del manifest["programs"]["dense/serve_write_params"]
    manifest["programs"]["dense/ghost_program"] = {"flops": 1.0}
    failures = P.check_budgets(program_audit_facts, manifest)
    assert any("missing from manifest" in m for m in failures)
    assert any("no longer audited" in m for m in failures)


def test_budget_roundtrip_clean(program_audit_facts, tmp_path):
    path = str(tmp_path / "budgets.json")
    P.save_budgets(program_audit_facts, path)
    assert P.check_budgets(program_audit_facts, P.load_budgets(path)) == []


# -- jaxpr weight-provenance unit pin ----------------------------------------

def test_weight_traffic_scan_multiplier():
    """A scanned matmul over a tagged (L, N, N) weight stack streams
    exactly L·N·N·4 weight bytes; the activation carry contributes 0."""
    L, N = 5, 16

    def g(x, ws):
        def step(c, w):
            return c @ w, None
        out, _ = jax.lax.scan(step, x, ws)
        return out

    traced = jax.jit(g).trace(SDS((N, N), jnp.float32),
                              SDS((L, N, N), jnp.float32))
    wbytes, dtypes = weight_traffic(traced.jaxpr, [False, True])
    assert wbytes == L * N * N * 4
    assert "float32" in dtypes
    # tag the activation instead: its operand bytes count, the stack's don't
    wbytes_x, _ = weight_traffic(traced.jaxpr, [True, False])
    assert wbytes_x == L * N * N * 4   # carry slice is (N,N) per iter too


# -- CLI ---------------------------------------------------------------------

def test_program_cli_json(tmp_path, monkeypatch, capsys):
    """`python -m repro.analysis program` end-to-end on a one-program
    enumeration: --update-budgets writes the manifest, a re-run diffs
    clean, and --json emits the machine-readable report CI annotates."""
    from repro.analysis.__main__ import main

    spec = P.ProgramSpec(
        name="unit/mm", fn=jax.jit(lambda a, b: a @ b),
        args=(SDS((8, 8), jnp.float32), SDS((8, 8), jnp.float32)),
        weight_argnums=(1,), meta={"single_host": True, "kind": "unit"})
    monkeypatch.setattr(P, "enumerate_specs", lambda models=None: [spec])
    path = str(tmp_path / "budgets.json")

    assert main(["program", "--update-budgets", "--budgets", path]) == 0
    capsys.readouterr()
    assert main(["program", "--json", "--budgets", path]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["ok"] and "unit/mm" in report["programs"]
    assert report["programs"]["unit/mm"]["flops"] > 0

    # drift the manifest: the CLI must exit non-zero and report it
    manifest = json.load(open(path))
    manifest["programs"]["unit/mm"]["flops"] *= 10
    json.dump(manifest, open(path, "w"))
    assert main(["program", "--json", "--budgets", path]) == 1
    report = json.loads(capsys.readouterr().out)
    assert not report["ok"] and report["budget_failures"]
