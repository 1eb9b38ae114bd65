"""Training launcher.

Two modes:
  * ``--edge`` (paper-scale): the Section-6 CIFAR/ResNet federated run with
    the Algorithm-1 controller, channel simulation and delay/energy
    accounting. ``--width 64`` (the default) is the paper's ResNet; runs on
    a TPU, or on the CPU at a small ``--width`` with the kernels
    interpreted.
  * datacenter (default): the LTFL federated step for an assigned
    architecture on an explicit device mesh — sized for real hardware; on
    CPU use --smoke to run a reduced config end-to-end.

Examples:
  PYTHONPATH=src python -m repro.launch.train --edge --rounds 50
  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke --steps 5
"""
from __future__ import annotations

import argparse
import json
import os


def edge_runner(*, scheme="ltfl", devices: int = 30,
                train_samples: int = 18000, test_samples: int = 2000,
                batch_size: int = 50, width: int = 64,
                non_iid_alpha: float = 0.0, engine=None, **engine_kwargs):
    """The Section-6 edge experiment, ready to ``run``: the pre-activation
    ResNet with stem ``width`` and groups (w, 2w, 4w, 8w) — ``width=64``
    is the paper's net — on seeded synthetic CIFAR-shaped data, with
    ``devices`` clients under the Table 2 wireless parameters.
    ``scheme`` is a name in ``ALL_SCHEMES`` or a scheme instance;
    ``engine`` is the runner class (default ``FedRunner``);
    ``engine_kwargs`` go to it. ``train_samples`` must cover the
    clients' shard sizes (up to ``LTFLConfig.samples_max`` each)."""
    import jax
    from repro.configs.base import LTFLConfig
    from repro.configs.ltfl_paper import ResNetConfig
    from repro.data import ArrayDataset, synthetic_cifar
    from repro.fed import ALL_SCHEMES, FedRunner
    from repro.models.resnet import ResNet

    ltfl = LTFLConfig(num_devices=devices)
    imgs, labels = synthetic_cifar(train_samples, seed=ltfl.seed)
    timgs, tlabels = synthetic_cifar(test_samples, seed=ltfl.seed + 1)
    train = ArrayDataset({"images": imgs, "labels": labels})
    test = ArrayDataset({"images": timgs, "labels": tlabels})
    model = ResNet(ResNetConfig(stem_channels=width,
                                group_channels=(width, width * 2,
                                                width * 4, width * 8)))
    params = model.init(jax.random.PRNGKey(ltfl.seed))
    if isinstance(scheme, str):
        scheme = ALL_SCHEMES[scheme]()
    engine = FedRunner if engine is None else engine
    return engine(model, params, ltfl, train, test, scheme,
                  batch_size=batch_size, non_iid_alpha=non_iid_alpha,
                  seed=ltfl.seed, **engine_kwargs)


def run_edge(args) -> None:
    runner = edge_runner(scheme=args.scheme, devices=args.devices,
                         train_samples=args.train_samples,
                         test_samples=args.test_samples,
                         batch_size=args.batch_size, width=args.width,
                         non_iid_alpha=args.non_iid_alpha)
    runner.run(args.rounds, log_every=max(args.rounds // 20, 1))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(runner.history_dict(), f, indent=2)
        print(f"history -> {args.out}")


def run_datacenter(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.core.ltfl_step import make_fl_train_step
    from repro.models import build_model, make_train_batch
    from repro.optim import sgd

    arch = configs.get_arch(args.arch)
    if args.smoke:
        arch = configs.reduce_for_smoke(arch)
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    opt = sgd(args.lr)
    opt_state = opt.init(params)
    n_clients = args.clients
    step_fn = make_fl_train_step(model, opt, n_clients,
                                 prune_block=args.prune_block)
    comp_state = step_fn.init_comp_state(params)
    step = jax.jit(step_fn)
    seq = args.seq_len
    batch = make_train_batch(arch, n_clients * args.per_client_batch, seq)
    batch = jax.tree_util.tree_map(
        lambda x: x.reshape(n_clients, args.per_client_batch, *x.shape[1:]),
        batch)
    controls = {
        "rho": jnp.full((n_clients,), args.rho),
        "delta": jnp.full((n_clients,), float(args.delta)),
        "drop_prob": jnp.full((n_clients,), args.drop_prob),
        "weights": jnp.ones((n_clients,)) * 500.0,
    }
    for i in range(args.steps):
        params, opt_state, comp_state, metrics = step(
            params, opt_state, comp_state, batch, controls,
            jax.random.PRNGKey(i))
        print(f"step={i} " + " ".join(
            f"{k}={float(v):.4f}" for k, v in metrics.items()
            if np.ndim(v) == 0))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--edge", action="store_true")
    # edge mode
    ap.add_argument("--scheme", default="ltfl")
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--devices", type=int, default=30)
    ap.add_argument("--train-samples", type=int, default=18000)
    ap.add_argument("--test-samples", type=int, default=2000)
    ap.add_argument("--batch-size", type=int, default=50)
    ap.add_argument("--width", type=int, default=64,
                    help="ResNet stem width; groups (w, 2w, 4w, 8w)")
    ap.add_argument("--non-iid-alpha", type=float, default=0.0)
    ap.add_argument("--out", default="")
    # datacenter mode
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--per-client-batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--rho", type=float, default=0.25)
    ap.add_argument("--delta", type=int, default=8)
    ap.add_argument("--drop-prob", type=float, default=0.05)
    ap.add_argument("--prune-block", type=int, default=32)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.edge:
        run_edge(args)
    else:
        run_datacenter(args)


if __name__ == "__main__":
    main()
