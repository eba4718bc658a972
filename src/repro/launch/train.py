"""Distributed FL training driver (executes the fl_step on a real mesh).

On the container this runs on a small host mesh (set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` yourself for a 4×2
mesh); on a TPU pod the same code runs on ``make_production_mesh()``.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --layers 4 --d-model 128 --rounds 20 --data-axis 4 --model-axis 2
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.strategy import SelectionContext, get_strategy
from repro.configs.base import RuntimeConfig, get_arch, reduced
from repro.core.strategies import ProbeReport
from repro.data.synthetic import FederatedTaskConfig, SyntheticFederatedData
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import Model
from repro.sharding.fl_step import make_fl_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--per-client-batch", type=int, default=4)
    ap.add_argument("--strategy", default="ours_unified",
                    help="any registered strategy name (repro.api)")
    ap.add_argument("--budget", type=int, default=2)
    ap.add_argument("--lam", type=float, default=10.0)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--data-axis", type=int, default=0,
                    help="0 = use the production mesh (dry-run scale)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--production", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    # resolve the strategy up front: unknown names fail fast with the
    # registered list + nearest-match suggestion
    strategy = get_strategy(args.strategy)

    if args.production:
        mesh = make_production_mesh()
        cfg = get_arch(args.arch)
    else:
        d = args.data_axis or max(len(jax.devices()) // args.model_axis, 1)
        mesh = make_host_mesh(d, args.model_axis)
        cfg = reduced(get_arch(args.arch), n_layers=args.layers,
                      d_model=args.d_model)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=max(args.seq, 16)))
    clients = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                           if a in ("pod", "data")]))
    print(f"mesh={dict(mesh.shape)} cohort={clients} arch={cfg.name}")

    params = model.init(jax.random.PRNGKey(0))
    build = make_fl_train_step(model, mesh, zero3=True)
    step_fn, specs = build(jax.eval_shape(lambda: params))
    params = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P)))

    data = SyntheticFederatedData(FederatedTaskConfig(
        n_clients=clients, vocab_size=cfg.vocab_size, seq_len=args.seq,
        objective="lm", skew="feature"))
    L = model.n_selectable
    sizes = jnp.asarray(data.sizes[:clients].astype(np.float32))

    # selection probe runs on the simulator path (cheap, L floats/client)
    from repro.core.client import Client
    probe_client = Client(Model(cfg, RuntimeConfig(remat=False,
                                                   seq_chunk=max(args.seq, 16))))

    # the strategy's declared probe requirements trim the per-client probe
    reqs = tuple(k for k in ProbeReport.KEYS
                 if k in strategy.probe_requirements)

    for t in range(args.rounds):
        t0 = time.time()  # repro: allow[nondeterminism] -- round wall-clock telemetry only
        host_params = jax.device_get(params)
        if reqs:
            rows = [probe_client.probe(host_params, data.client_batch(i, 4),
                                       reqs)
                    for i in range(clients)]
            probe = ProbeReport.from_rows(rows)
        else:
            probe = ProbeReport(grad_sq_norms=np.zeros((clients, L)))
        ctx = SelectionContext(client_ids=np.arange(clients), round=t,
                               lam=args.lam, n_layers=L)
        masks = jnp.asarray(strategy.select(probe, args.budget, ctx))

        batch_np = np.stack([
            data.client_batch(i, args.per_client_batch)["tokens"]
            for i in range(clients)])
        batch = {"tokens": jnp.asarray(batch_np)}
        params, metrics = step_fn(params, batch, masks, sizes,
                                  jnp.float32(args.lr))
        print(f"[round {t:3d}] loss={float(metrics['loss']):.4f} "
              f"union={float(metrics['union_frac']):.2f} "
              f"({time.time() - t0:.2f}s)")  # repro: allow[nondeterminism] -- round wall-clock telemetry only


if __name__ == "__main__":
    main()
