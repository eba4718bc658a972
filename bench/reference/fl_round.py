"""Plain reference of the paper's federated round (Algorithm 1, §4.2), in
f32 at full precision, following the program's first rounds from the seed.

A round: each cohort client's per-layer squared gradient norms G on its
probe batch (all L layers); the (P1) selection by iterated conditional
modes under each client's budget, warm-started from the client's previous
mask; τ masked SGD steps per client; Eq.(5)-(7) aggregation and the global
step.  Parameters are stored in the configuration's dtype between steps
(they are bf16 parameters), and every step is computed in f32.  The
configuration's family (``bench/reference/<family>.py``) gives the loss
and the stacked segments whose rows are the round's layers.

The cohort draw replays the program's documented sampling: a
``numpy.random.RandomState(seed)`` drawing ``choice(N, cohort,
replace=False)`` once per round; the data comes from the benchmark's own
task, drawn in the same per-client order (probe rows, then update rows).

After the first rounds, whose selection it makes as the algorithm does,
it follows rounds whose prefix cut is forced (``checked_schedule``): the
window can meet any cut, and each cut is a program of its own.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import family_module, segment_slices


# -- (P1): λ-regularised layer selection, §4.2 -------------------------------

def pick_budget(util: np.ndarray, budget: float) -> np.ndarray:
    """Greedy knapsack with unit layer costs: layers by utility, best first,
    while they fit; past the first pick, no layer of utility ≤ 0."""
    m = np.zeros(util.shape[0], np.float32)
    spent = 0.0
    for l in np.argsort(-util):
        if util[l] <= 0 and spent > 0:
            break
        if spent + 1.0 <= budget + 1e-9:
            m[l] = 1.0
            spent += 1.0
    return m


def solve_p1(G: np.ndarray, budgets: np.ndarray, lam: float,
             init: np.ndarray, max_iters: int = 50) -> np.ndarray:
    """Block coordinate ascent on Σ G·m − (λ/2) Σ_i Σ_{j≠i} ‖m_i − m_j‖₁:
    client i's conditional utility of layer l is
    G_il − λ((n−1) − 2·Σ_{j≠i} m_jl)."""
    n = G.shape[0]
    masks = init.astype(np.float32).copy()
    for _ in range(max_iters):
        changed = False
        for i in range(n):
            others = masks.sum(0) - masks[i]
            new = pick_budget(G[i] - lam * ((n - 1) - 2.0 * others), budgets[i])
            if not np.array_equal(new, masks[i]):
                masks[i] = new
                changed = True
        if not changed:
            break
    return masks


def eq7_weights(masks: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """w_il = d_i m_il / Σ_j d_j m_jl (0 where no client selected l)."""
    num = masks * sizes[:, None].astype(np.float64)
    den = num.sum(0, keepdims=True)
    return np.where(den > 0, num / np.where(den > 0, den, 1.0), 0.0)


# -- the checked rounds -----------------------------------------------------

NATURAL_ROUNDS = 3      # rounds 0-2: the (P1) selection as the algorithm makes it


def checked_schedule(n_layers: int) -> dict:
    """Round -> the prefix cut forced on it, for the checked rounds after
    the natural ones: every cut the window can meet, each in a round that
    another round follows (so it runs the fused probe+update program), and
    cut 0 once more as the last round (the update-only program)."""
    cuts = list(range(n_layers)) + [0]
    return {NATURAL_ROUNDS + k: c for k, c in enumerate(cuts)}


def checked_rounds(n_layers: int) -> int:
    return NATURAL_ROUNDS + len(checked_schedule(n_layers))


def forced_masks(budgets, cut: int, n_layers: int) -> np.ndarray:
    """Client i trains layers cut .. cut + R_i - 1 (clipped at the top), so
    the round's prefix cut is ``cut``."""
    m = np.zeros((len(budgets), n_layers), np.float32)
    for r, b in enumerate(budgets):
        m[r, cut:min(cut + max(int(round(float(b))), 1), n_layers)] = 1.0
    return m


# -- the round --------------------------------------------------------------

@dataclass
class Readings:
    """What a run of rounds reports, from the program or the reference."""
    losses: list            # per round: mean over clients of mean step loss
    masks: list             # per round: (cohort, L) 0/1, the (P1) selection
    stats: list             # per round: (probed, L) squared gradient norms
    updates: list           # per round: leaf -> ‖(θ_t − θ_{t+1})/lr‖
    change: dict            # leaf -> ‖θ_R − θ0‖ after the last round


class FLReference:
    def __init__(self, config: dict, fl: dict, lowp=None):
        self.family = family_module(config, "reference")
        self.a = self.family.Arch.from_config(config)
        self.segments = self.family.segments(config)
        self.slices = segment_slices(self.segments)
        self.fl = fl
        self.lowp = lowp
        self.store = jnp.dtype(config["torch_dtype"])
        self._grad = jax.jit(jax.value_and_grad(self._loss, argnums=0))

    def _loss(self, blocks, frozen, batch):
        return self.family.class_loss({**frozen, **blocks}, batch, self.a,
                                      self.lowp)

    def _stored(self, x):
        return x.astype(self.store).astype(jnp.float32)

    def _layer_rows(self, per_layer, leaf: str, ndim: int):
        """A per-layer vector's entries for a stacked leaf's rows, shaped
        to broadcast over the leaf."""
        rows = per_layer[self.slices[leaf.split("/", 1)[0]]]
        return rows.reshape((-1,) + (1,) * (ndim - 1))

    @partial(jax.jit, static_argnums=0)
    def _sgd(self, blocks, grads, mask, lr):
        """θ_l ← θ_l − lr·m_l·g_l, stored back in the parameter dtype."""
        return {k: self._stored(v - lr * self._layer_rows(mask, k, v.ndim)
                                * grads[k]) for k, v in blocks.items()}

    def layer_sq_norms(self, grads: dict) -> np.ndarray:
        """Σ g² of each layer, in layer order over the segments."""
        return np.asarray(jnp.concatenate([
            sum(jnp.sum(jnp.square(g).reshape(g.shape[0], -1), 1)
                for k, g in grads.items() if k.split("/", 1)[0] == seg)
            for seg, _ in self.segments]), np.float64)

    def run(self, params: dict, task, cohorts: list, budgets, rounds: int,
            forced: dict | None = None) -> Readings:
        """``params``: flat dict of the initial parameters (any float
        dtype); ``task``: a fresh benchmark task of the run's seed;
        ``cohorts``: each round's cohort ids; ``forced``: round -> the
        prefix cut forced on it (``forced_masks``), where the selection is
        not made."""
        fl, forced = self.fl, forced or {}
        lr, lam, tau = float(fl["lr"]), float(fl["lam"]), int(fl["local_steps"])
        B, period = int(fl["batch_size"]), int(fl["selection_period"])
        L = sum(n for _, n in self.segments)
        with jax.default_matmul_precision("highest"):
            params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
            blocks = {k: v for k, v in params.items()
                      if k.split("/", 1)[0] in self.slices}
            frozen = {k: v for k, v in params.items() if k not in blocks}
            theta0 = blocks
            warm: dict[int, np.ndarray] = {}
            stats: dict[int, np.ndarray] = {}
            out = Readings([], [], [], [], {})
            for t in range(rounds):
                cohort = [int(i) for i in cohorts[t]]
                if t % period == 0:
                    stats.clear()
                probe_ids = [i for i in cohort if i not in stats]
                if probe_ids:
                    pb = task.cohort_batches(probe_ids, B, 1)
                    for r, i in enumerate(probe_ids):
                        _, g = self._grad(blocks, frozen, _row(pb, r, 0))
                        stats[i] = self.layer_sq_norms(g)
                out.stats.append(np.stack([stats[i] for i in probe_ids])
                                 if probe_ids else np.zeros((0, L)))
                ub = task.cohort_batches(cohort, B, tau)
                bud = np.asarray([budgets[i] for i in cohort], np.float64)
                if t in forced:
                    masks = forced_masks(bud, forced[t], L)
                else:
                    G = np.stack([stats[i] for i in cohort]).astype(np.float32)
                    init = np.stack([warm[i] if i in warm else
                                     pick_budget(G[r].astype(np.float64), bud[r])
                                     for r, i in enumerate(cohort)])
                    masks = solve_p1(G.astype(np.float64), bud, lam, init)
                    for r, i in enumerate(cohort):
                        warm[i] = masks[r]
                deltas, losses = [], []
                for r in range(len(cohort)):
                    th, ls = blocks, []
                    m = jnp.asarray(masks[r])
                    for k in range(tau):
                        loss, g = self._grad(th, frozen, _row(ub, r, k))
                        ls.append(loss)
                        th = self._sgd(th, g, m, lr)
                    deltas.append({n: (blocks[n] - th[n]) / lr for n in blocks})
                    losses.append(np.mean([float(x) for x in ls]))
                W = eq7_weights(masks, task.sizes[cohort])
                new = {}
                for n, v in blocks.items():
                    upd = sum(self._layer_rows(jnp.asarray(W[r], jnp.float32),
                                               n, v.ndim) * deltas[r][n]
                              for r in range(len(cohort)))
                    new[n] = self._stored(v - lr * upd)
                out.updates.append({n: float(jnp.linalg.norm(
                    (blocks[n] - new[n]) / lr)) for n in blocks})
                blocks = new
                out.losses.append(float(np.mean(losses)))
                out.masks.append(masks)
            out.change = {n: float(jnp.linalg.norm(blocks[n] - theta0[n]))
                          for n in blocks}
        return out


def _row(batches: dict, r: int, k: int) -> dict:
    return {n: jnp.asarray(v[r, k]) for n, v in batches.items()}


def draw_cohorts(seed: int, n_clients: int, cohort: int, rounds: int) -> list:
    rng = np.random.RandomState(seed)
    return [rng.choice(n_clients, size=cohort, replace=False)
            for _ in range(rounds)]


# -- the comparison ---------------------------------------------------------

def norm_gap(obs: dict, ref: dict, grad_ref: dict) -> float:
    """Worst leaf's |‖obs‖ − ‖ref‖| over max(‖ref‖, median leaf ‖ref‖),
    from per-leaf norms.  Leaves whose reference gradient norm is under a
    thousandth of the median leaf's are nought to rounding and left out."""
    return max(leaf_gaps(obs, ref, grad_ref).values())


def leaf_gaps(obs: dict, ref: dict, grad_ref: dict) -> dict:
    """Each kept leaf's gap, as ``norm_gap`` takes its worst."""
    med_g = float(np.median(list(grad_ref.values())))
    keep = [n for n in ref if grad_ref[n] >= 1e-3 * med_g]
    med = float(np.median([ref[n] for n in keep]))
    return {n: abs(obs[n] - ref[n]) / max(ref[n], med, 1e-30) for n in keep}


def stat_gap(obs: np.ndarray, ref: np.ndarray) -> float:
    """Worst entry's |obs − ref| over max(ref, the round's median ref entry):
    one round's probe statistics, (probed, L)."""
    if not ref.size:
        return 0.0
    med = float(np.median(ref))
    return float(np.max(np.abs(np.asarray(obs, np.float64) - ref)
                        / np.maximum(np.maximum(ref, med), 1e-30)))


def compare(obs: Readings, ref: Readings) -> dict:
    """The numbers ``correct`` holds against their limits.  The selection
    is compared where the algorithm made it (the natural rounds); every
    other number over every checked round."""
    grad_ref = ref.updates[0]
    return {
        "loss_gap": max(abs(o - r) / abs(r)
                        for o, r in zip(obs.losses, ref.losses)),
        "first_update_gap": norm_gap(obs.updates[0], ref.updates[0], grad_ref),
        "round_update_gap": max(norm_gap(o, r, grad_ref) for o, r in
                                zip(obs.updates[1:], ref.updates[1:])),
        "change_gap": norm_gap(obs.change, ref.change, grad_ref),
        "probe_gap": max(stat_gap(o, r) for o, r in zip(obs.stats, ref.stats)),
        "mask_rows_differing": float(sum(
            int((np.asarray(o) != np.asarray(r)).any(1).sum())
            for o, r in list(zip(obs.masks, ref.masks))[:NATURAL_ROUNDS])),
    }


def per_round(obs: Readings, ref: Readings) -> dict:
    """Each checked round's gaps, in order, and the leaf that read worst:
    where in the checked rounds a number reads what it reads."""
    grad_ref = ref.updates[0]

    def worst(o, r):
        gaps = leaf_gaps(o, r, grad_ref)
        return max(gaps, key=gaps.get)
    return {
        "loss": [abs(o - r) / abs(r) for o, r in zip(obs.losses, ref.losses)],
        "update": [norm_gap(o, r, grad_ref)
                   for o, r in zip(obs.updates, ref.updates)],
        "update_leaf": [worst(o, r) for o, r in zip(obs.updates, ref.updates)],
        "probe": [stat_gap(o, r) for o, r in zip(obs.stats, ref.stats)],
    }
