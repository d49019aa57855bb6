"""MLI-algorithm launcher: streaming epochs with checkpoint/resume.

The streaming counterpart of ``repro.launch.train`` for the paper's
algorithms: data arrives as per-epoch minibatch windows from a
:class:`repro.data.pipeline.BatchIterator` (never fully resident), the
:class:`repro.core.runner.DistributedRunner` iterates them on the device
mesh, and a :class:`repro.core.runner.CheckpointPolicy` makes the run
survive being killed — relaunching with ``--resume`` continues from the
newest snapshot bit-for-bit.

Examples (CPU container; add XLA_FLAGS=--xla_force_host_platform_device_count=8
for a multi-device mesh):

    PYTHONPATH=src python -m repro.launch.fit --algorithm logreg \\
        --epochs 8 --rows-per-epoch 256 --features 16 --chunks-per-epoch 4 \\
        --schedule allreduce --ckpt-dir /tmp/mli-logreg --ckpt-every 2

    # kill it mid-run, then:
    PYTHONPATH=src python -m repro.launch.fit --algorithm logreg \\
        --epochs 8 --rows-per-epoch 256 --features 16 --chunks-per-epoch 4 \\
        --schedule allreduce --ckpt-dir /tmp/mli-logreg --resume

Multi-host (hosts simulated as CPU subprocesses, never on an accelerator):

    # BSP: one global 2x4-device mesh, gloo collectives, lock-step rounds
    PYTHONPATH=src python -m repro.launch.fit --algorithm logreg \\
        --epochs 4 --hosts 2 --devices-per-host 4

    # SSP: independent hosts exchanging weights with staleness bound 2;
    # --elastic also restarts the world (resized) if a host dies
    PYTHONPATH=src python -m repro.launch.fit --algorithm logreg \\
        --epochs 4 --hosts 3 --staleness 2 --elastic \\
        --ckpt-dir /tmp/mli-ssp --ckpt-every 1
"""
from __future__ import annotations

import argparse
import os
import sys

from repro.core import hostmesh

# the multi-host BSP lane must join the mesh BEFORE anything touches the
# jax backend; a no-op without the REPRO_* launcher contract in place
_HOST_INFO = hostmesh.initialize_from_env()

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step
from repro.core.algorithms.kmeans import KMeans, KMeansParameters
from repro.core.algorithms.logistic_regression import (
    LogisticRegressionAlgorithm,
    LogisticRegressionParameters,
)
from repro.core.compat import make_mesh
from repro.core.optimizer import MinibatchSGD, MinibatchSGDParameters
from repro.core.runner import CheckpointPolicy
from repro.data import BatchIterator
from repro.launch.compile_cache import enable_compile_cache

ALGORITHMS = ("logreg", "linreg", "kmeans", "pipeline")


def make_source(algorithm: str, rows: int, features: int, seed: int):
    """Deterministic per-step window generator — a pure function of the
    step, which is what makes ``--resume`` exact."""
    if algorithm == "logreg":
        def source(step: int):
            rng = np.random.default_rng(seed * 100_003 + step)
            w = np.linspace(-1, 1, features).astype(np.float32)
            X = rng.normal(size=(rows, features)).astype(np.float32)
            y = (X @ w > 0).astype(np.float32)
            return {"data": np.concatenate([y[:, None], X], 1)}
    elif algorithm == "linreg":
        def source(step: int):
            rng = np.random.default_rng(seed * 100_003 + step)
            w = np.arange(1, features + 1, dtype=np.float32) / features
            X = rng.normal(size=(rows, features)).astype(np.float32)
            y = X @ w + 0.01 * rng.normal(size=rows).astype(np.float32)
            return {"data": np.concatenate([y[:, None], X], 1)}
    else:
        def source(step: int):
            rng = np.random.default_rng(seed * 100_003 + step)
            k = 4
            centers = np.stack([np.full(features, 2.0 * (i - (k - 1) / 2))
                                for i in range(k)]).astype(np.float32)
            idx = rng.integers(0, k, size=rows)
            X = centers[idx] + 0.3 * rng.normal(size=(rows, features))
            return {"data": X.astype(np.float32)}
    return source


def run_pipeline(args, mesh, ckpt, resume) -> None:
    """The Fig. A2 flagship scenario as ONE object: raw labeled text →
    NGrams → TfIdf → Standardizer → logistic regression, trained from
    streamed windows with the whole artifact (featurizer statistics +
    model + stream position) in every atomic checkpoint."""
    from repro.core.mltable import MLTable
    from repro.data import synth_labeled_text
    from repro.features import NGrams, Standardizer, TfIdf
    from repro.pipeline import Pipeline
    from repro.serve import ModelPredictor, PredictRequest

    rows = synth_labeled_text(n_docs=args.rows_per_epoch, seed=args.seed)
    raw = MLTable.from_rows(rows, names=["label", "text"], num_partitions=4)
    pipe = Pipeline([
        NGrams(n=1, top=args.features, column="text"),
        TfIdf(),
        Standardizer(),
        LogisticRegressionAlgorithm(
            learning_rate=args.lr, local_batch_size=args.local_batch_size,
            schedule=args.schedule),
    ], mesh=mesh, num_shards=None if mesh is not None else args.num_shards)
    fitted = pipe.fit_stream(raw, num_epochs=args.epochs,
                             chunks_per_epoch=args.chunks_per_epoch,
                             checkpoint=ckpt, resume=resume)
    table = fitted.transform(raw)
    X = jnp.asarray(table.data)
    acc = float(jnp.mean(fitted.model.predict(X[:, 1:]) == X[:, 0]))
    print(f"done: pipeline train acc {acc:.3f} "
          f"({table.num_rows} rows x {table.num_cols - 1} features)")
    served = ModelPredictor(fitted, max_batch=8)
    req = served.submit(PredictRequest(features=rows[0][1]))
    served.flush()
    print(f"served raw text -> class {float(req.result[0]):.0f} "
          f"(label {rows[0][0]:.0f})")


def _drive_hosts(args, argv) -> None:
    """Driver mode: re-exec this module once per host under the elastic
    controller.  Children carry ``REPRO_HOST_ID`` and skip this branch."""
    import tempfile

    from repro.launch.elastic import ElasticController

    child = [sys.executable, "-m", "repro.launch.fit"] + \
        list(argv if argv is not None else sys.argv[1:])
    if args.staleness is not None and not args.exchange_dir:
        exchange = (os.path.join(args.ckpt_dir, "exchange") if args.ckpt_dir
                    else tempfile.mkdtemp(prefix="mli-exchange-"))
        child += ["--exchange-dir", exchange]
    ctl = ElasticController(
        child, args.hosts, devices_per_host=args.devices_per_host,
        max_restarts=2 if args.elastic else 0,
        min_hosts=1, timeout=600.0,
        global_mesh=args.staleness is None)
    report = ctl.run()
    for gen in report.generations:
        tag = f"generation {gen.index} ({gen.num_hosts} hosts)"
        for e in sorted(gen.exits, key=lambda x: x.host_id):
            for line in e.stdout.strip().splitlines():
                print(f"[{tag} h{e.host_id}] {line}")
    if report.resized:
        print(f"elastic: {len(report.generations)} generations, restart "
              f"latency {[f'{s:.2f}s' for s in report.restart_seconds]}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--algorithm", required=True, choices=ALGORITHMS)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--rows-per-epoch", type=int, default=256)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--chunks-per-epoch", type=int, default=4)
    ap.add_argument("--schedule", default="allreduce",
                    choices=("allreduce", "gather_broadcast", "reduce_scatter"))
    ap.add_argument("--num-shards", type=int, default=4,
                    help="emulated partitions when only one device is visible")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=1)
    ap.add_argument("--keep", type=int, default=None,
                    help="retain only the newest N checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest checkpoint in --ckpt-dir")
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--local-batch-size", type=int, default=8)
    ap.add_argument("--k", type=int, default=4, help="k-means cluster count")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hosts", type=int, default=1,
                    help="spawn N hosts simulated as CPU subprocesses "
                         "(multi-host mesh; BSP unless --staleness is "
                         "given); they never use an accelerator")
    ap.add_argument("--devices-per-host", type=int, default=2)
    ap.add_argument("--staleness", type=int, default=None,
                    help="stale-synchronous lane with this bound (0 = "
                         "lock-step BSP over the exchange store)")
    ap.add_argument("--elastic", action="store_true",
                    help="restart the world (resized) when a host dies; "
                         "survivors resume from --ckpt-dir")
    ap.add_argument("--exchange-dir", default=None,
                    help="shared SSP exchange directory (defaults under "
                         "--ckpt-dir or a fresh temp dir)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.hosts > 1 and "REPRO_HOST_ID" not in os.environ:
        _drive_hosts(args, argv)
        return

    devices = jax.devices()
    mesh = make_mesh((len(devices),), ("data",)) if len(devices) > 1 else None
    where = (f"{len(devices)}-device mesh" if mesh is not None
             else f"{args.num_shards} emulated partitions")
    if _HOST_INFO.multihost:
        where += (f" ({hostmesh.num_hosts()} hosts x "
                  f"{len(jax.local_devices())} local devices)")
    print(f"fit: {args.algorithm} | {where} | schedule={args.schedule} | "
          f"{args.epochs} epochs x {args.rows_per_epoch} rows x "
          f"{args.chunks_per_epoch} chunks")

    ssp = args.staleness is not None and int(os.environ.get(
        "REPRO_NUM_HOSTS", "1")) > 1
    host = int(os.environ.get("REPRO_HOST_ID", "0"))
    elastic_resume = args.elastic and os.environ.get("REPRO_RESUME") == "1"
    store = None
    ckpt_dir = args.ckpt_dir
    seed = args.seed
    if ssp:
        # SSP hosts are independent programs: each streams its own data
        # (seed offset by rank), checkpoints into its own subdirectory, and
        # exchanges through the shared ParamStore
        from repro.core.exchange import ParamStore
        from repro.testing.chaos import ChaosInjector

        if not args.exchange_dir:
            ap.error("--staleness with --hosts needs --exchange-dir "
                     "(the driver injects one automatically)")
        n = int(os.environ["REPRO_NUM_HOSTS"])
        store = ParamStore(args.exchange_dir, host, n,
                           keep=args.staleness + 2)
        if ckpt_dir:
            ckpt_dir = os.path.join(ckpt_dir, f"h{host}")
        seed = args.seed + 7919 * host

    ckpt = None
    if ckpt_dir:
        ckpt = CheckpointPolicy(ckpt_dir, every_epochs=args.ckpt_every,
                                keep=args.keep)
    resume = bool((args.resume or elastic_resume) and ckpt_dir
                  and latest_step(ckpt_dir) is not None)
    if args.resume and not resume:
        print("no checkpoint found; starting fresh")
    if resume:
        print(f"resuming from step {latest_step(ckpt_dir)} "
              f"in {ckpt_dir}")

    if args.algorithm == "pipeline":
        if ssp or _HOST_INFO.multihost:
            ap.error("--hosts supports logreg | linreg | kmeans")
        run_pipeline(args, mesh, ckpt, resume)
        return

    source = make_source(args.algorithm, args.rows_per_epoch, args.features,
                         seed)
    stream = BatchIterator(source, mesh=mesh)
    common = dict(num_epochs=args.epochs, num_shards=args.num_shards,
                  chunks_per_epoch=args.chunks_per_epoch, checkpoint=ckpt,
                  resume=resume)
    if ssp:
        injector = ChaosInjector.from_env(host_id=host, store=store)
        stream = injector.wrap_stream(stream)
        common.update(store=store, staleness=args.staleness)
        if args.algorithm == "kmeans":
            common["chunks_per_epoch"] = 1
    elif args.elastic:
        common["allow_resize"] = True
    holdout = source(10**9)["data"]  # never reached by training steps

    if args.algorithm == "logreg":
        p = LogisticRegressionParameters(
            learning_rate=args.lr, local_batch_size=args.local_batch_size,
            schedule=args.schedule)
        model = LogisticRegressionAlgorithm(p).fit_stream(stream, **common)
        X, y = jnp.asarray(holdout[:, 1:]), jnp.asarray(holdout[:, 0])
        acc = float(jnp.mean(model.predict(X) == y))
        print(f"done: holdout loss {float(model.loss(X, y)):.4f} "
              f"acc {acc:.3f}")
    elif args.algorithm == "linreg":
        def grad(vec, w):
            x = vec[1:]
            return x * (jnp.dot(x, w) - vec[0])

        p = MinibatchSGDParameters(
            w_init=jnp.zeros(args.features, jnp.float32), grad=grad,
            learning_rate=args.lr * 0.1, schedule=args.schedule)
        w = MinibatchSGD(p).apply_stream(stream, **common)
        X, y = jnp.asarray(holdout[:, 1:]), jnp.asarray(holdout[:, 0])
        mse = float(jnp.mean((X @ w - y) ** 2))
        print(f"done: holdout mse {mse:.5f}")
    else:
        p = KMeansParameters(k=args.k, seed=args.seed, schedule=args.schedule)
        model = KMeans(p).fit_stream(stream, **common)
        inertia = float(model.inertia(jnp.asarray(holdout)))
        print(f"done: holdout inertia {inertia:.2f}")
    print(f"stream position: step {stream.step}")


if __name__ == "__main__":
    main()
