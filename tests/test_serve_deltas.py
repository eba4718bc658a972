"""Personalized-delta serving (DESIGN.md §9): parity of the batched
delta/dense paths against private-params-alone decoding, overlay capacity
bookkeeping, checkpoint delta extraction, and the one-program pin."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RuntimeConfig, get_arch, reduced
from repro.core.client import clear_jit_cache, jit_cache_stats
from repro.launch.serve import Request, SlotServer, demo_store
from repro.models.model import Model
from repro.serve import DeltaOverlay, DeltaStore, delta_from_params


def _world(n_layers=3, d_model=64):
    cfg = reduced(get_arch("tinyllama_1_1b"), n_layers=n_layers,
                  d_model=d_model)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _decode_alone(model, params, prompt, max_new, max_seq):
    """The oracle: one request, scalar-position cache, no batching."""
    cache = model.init_cache(1, max_seq)
    out = []
    for t in range(len(prompt) + max_new - 1):
        cur = prompt[t] if t < len(prompt) else out[-1]
        logits, cache = model.decode_step(params, jnp.asarray([cur]),
                                          jnp.int32(t), cache)
        if t >= len(prompt) - 1:
            out.append(int(jnp.argmax(logits[0])))
    return out


def _requests(cfg, n, plen=4, max_new=5, users=0, seed=1):
    rng = np.random.RandomState(seed)
    return [Request(i, rng.randint(0, cfg.vocab_size, plen).tolist(), max_new,
                    user_id=(i % users if users else -1)) for i in range(n)]


def test_shared_staggered_matches_alone():
    """7 requests through 3 slots admit at staggered positions; every
    generation equals decoding that request alone (per-slot positions)."""
    model, params = _world()
    reqs = _requests(model.cfg, 7)
    prompts = {r.rid: list(r.prompt) for r in reqs}
    server = SlotServer(model, params, slots=3, max_seq=16)
    done, stats = server.run(reqs)
    assert len(done) == 7 and stats["gen_tokens"] == 35
    for r in done:
        assert r.generated == _decode_alone(model, params, prompts[r.rid],
                                            r.max_new, 16), r.rid


def test_delta_staggered_matches_private_alone():
    """The batched delta path == decoding each request alone against the
    user's materialised private params — with different users' deltas
    resident in the same batch."""
    model, params = _world()
    store = demo_store(model, params, users=3, layers_per_user=2, seed=0)
    reqs = _requests(model.cfg, 7, users=3)
    prompts = {r.rid: (list(r.prompt), r.user_id) for r in reqs}
    server = SlotServer(model, params, slots=3, max_seq=16, mode="delta",
                        store=store)
    done, _ = server.run(reqs)
    assert len(done) == 7
    for r in done:
        prompt, uid = prompts[r.rid]
        private = store.materialize(params, uid)
        assert r.generated == _decode_alone(model, private, prompt,
                                            r.max_new, 16), r.rid


def test_dense_staggered_matches_private_alone():
    """The vmapped per-slot-params baseline hits the same oracle."""
    model, params = _world()
    store = demo_store(model, params, users=3, layers_per_user=1, seed=2)
    reqs = _requests(model.cfg, 5, users=3)
    prompts = {r.rid: (list(r.prompt), r.user_id) for r in reqs}
    server = SlotServer(model, params, slots=2, max_seq=16, mode="dense",
                        store=store)
    done, _ = server.run(reqs)
    assert len(done) == 5
    for r in done:
        prompt, uid = prompts[r.rid]
        private = store.materialize(params, uid)
        assert r.generated == _decode_alone(model, private, prompt,
                                            r.max_new, 16), r.rid


def test_one_program_serves_mixed_deltas():
    """The whole mixed-user run compiles exactly one delta-decode program:
    the overlay is data, not program structure (acceptance pin)."""
    clear_jit_cache()
    model, params = _world()
    store = demo_store(model, params, users=4, layers_per_user=2, seed=0)
    server = SlotServer(model, params, slots=3, max_seq=16, mode="delta",
                        store=store)
    done, _ = server.run(_requests(model.cfg, 8, users=4))
    assert len(done) == 8
    programs = jit_cache_stats()["programs"]
    assert programs["serve_decode_delta"] == 1
    assert programs["serve_reset_slot"] == 1
    clear_jit_cache()


def test_overlay_capacity_admit_release():
    model, params = _world()
    tuned = dict(params)
    tuned["blocks"] = {k: np.asarray(v, np.float32) + 0.01
                       for k, v in params["blocks"].items()}
    rec = delta_from_params(params, tuned, model.cfg, layers=[0, 1])
    ov = DeltaOverlay(model, capacity=1)
    assert ov.try_admit(0, rec)
    assert ov.n_entries == 2
    assert not ov.try_admit(1, rec)          # layer capacity exhausted
    assert ov.n_entries == 2                 # failed admit wrote nothing
    ov.release(0)
    assert ov.try_admit(1, rec)
    dev = ov.device()
    assert np.asarray(dev["slots"]).max() == 1


def _eqns(jaxpr, skip=("pallas_call",)):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, except
    inside the primitives named in ``skip``."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in skip:
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, skip)


def test_delta_decode_reads_overlay_in_place(monkeypatch):
    """The delta decode's jaxpr with the Pallas kernel forced: nothing is
    padded outside the kernel, and the overlay leaves and the base
    projections they correct enter the decode scan as loop constants (the
    kernel reads them in place), not as its per-layer ``xs``."""
    from repro.kernels import ops
    from repro.models.model import delta_projections
    monkeypatch.setattr(ops, "_resolve_mode", lambda mode, interpret: "pallas")
    model, params = _world()
    delta = DeltaOverlay(model, capacity=2).device()
    cache = model.init_cache(3, 16, per_slot=True)
    vec = jnp.zeros(3, jnp.int32)
    closed = jax.make_jaxpr(lambda p, c, d: model.decode_step(
        p, vec, vec, c, delta=d))(params, cache, delta)
    eqns = list(_eqns(closed.jaxpr))
    assert any(e.primitive.name == "pallas_call" for e in eqns)
    assert not [e for e in eqns if e.primitive.name == "pad"]

    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path((params, cache, delta))[0]]
    wanted = ({f"[0]['blocks']['{n}']" for n in
               delta_projections(params["blocks"], delta["leaves"])}
              | {f"[2]['leaves']['{n}']" for n in delta["leaves"]})
    read_in_place = [v for v, n in zip(closed.jaxpr.invars, paths)
                     if n in wanted]
    assert len(read_in_place) == len(wanted) == 6 + len(delta["leaves"])
    scan, = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
    consts = set(scan.invars[:scan.params["num_consts"]])
    xs = set(scan.invars[scan.params["num_consts"]
                         + scan.params["num_carry"]:])
    assert all(v in consts and v not in xs for v in read_in_place)


def test_delta_record_autodetect_and_materialize():
    """layers=None detects exactly the perturbed rows; store.materialize
    reproduces the tuned tree on those rows and leaves the rest alone."""
    model, params = _world()
    cfg = model.cfg
    tuned = dict(params)
    tuned["blocks"] = {
        k: np.asarray(v, np.float32)
        + 0.05 * (np.arange(v.shape[0]) == 1).reshape(
            (-1,) + (1,) * (np.ndim(v) - 1))
        for k, v in params["blocks"].items()}
    rec = delta_from_params(params, tuned, cfg)
    assert rec.layers.tolist() == [1]
    store = DeltaStore(cfg)
    store.put(7, rec)
    mat = store.materialize(params, 7)
    for k in params["blocks"]:
        np.testing.assert_allclose(np.asarray(mat["blocks"][k], np.float32),
                                   tuned["blocks"][k], atol=1e-6)
    # unknown user falls back to base params untouched
    assert store.materialize(params, 99) is params


def test_extract_delta_from_round_checkpoint(tmp_path):
    """FL round checkpoint (wrapped ``params/`` tree) → DeltaRecord."""
    from repro.ckpt import extract_delta, save_checkpoint
    model, params = _world()
    tuned = jax.tree.map(lambda x: x, params)
    tuned["blocks"] = {
        k: jnp.asarray(np.asarray(v, np.float32)
                       + 0.05 * (np.arange(v.shape[0]) == 2).reshape(
                           (-1,) + (1,) * (np.ndim(v) - 1))).astype(v.dtype)
        for k, v in params["blocks"].items()}
    save_checkpoint(str(tmp_path), 3, {"params": tuned, "round": 3})
    rec = extract_delta(str(tmp_path), params, model.cfg)
    assert rec.layers.tolist() == [2]
    rows, leaves = rec.segments["blocks"]
    assert rows.tolist() == [2]
    got = np.asarray(params["blocks"]["attn_wq"], np.float32)[2] \
        + leaves["attn_wq"][0]
    np.testing.assert_allclose(
        got, np.asarray(tuned["blocks"]["attn_wq"], np.float32)[2],
        atol=1e-6)
