"""Logistic regression via partition-local SGD (paper §IV-A, Fig. A4).

Library convention (as in Fig. A4): the input MLNumericTable carries the
label in column 0 and the features in columns 1..d.  The gradient closure is
literally the paper's:

    def gradient(vec, w):
        x = vec[1:]
        return x * (sigmoid(x · w) - vec[0])

and training is one call into the SGD optimizer, which iterates through
:class:`repro.core.runner.DistributedRunner` — ``params.schedule`` selects
the §IV-A collective schedule of the per-round weight averaging.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import trace
from repro.core.collectives import CollectiveSchedule
from repro.core.interfaces import (
    Model,
    NumericAlgorithm,
    Searchable,
    StreamFitable,
)
from repro.core.numeric_table import MLNumericTable
from repro.core.optimizer import (
    GradientDescent,
    GradientDescentParameters,
    StochasticGradientDescent,
    StochasticGradientDescentParameters,
    sgd_trial_round,
    soft_threshold,
)

__all__ = [
    "LogisticRegressionParameters",
    "LogisticRegressionModel",
    "LogisticRegressionAlgorithm",
    "LogisticRegression",
]


def sigmoid(z: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.sigmoid(z)


@dataclasses.dataclass
class LogisticRegressionParameters:
    learning_rate: float = 0.5
    max_iter: int = 10
    l2: float = 0.0
    l1: float = 0.0
    local_batch_size: int = 1
    schedule: Union[str, CollectiveSchedule] = CollectiveSchedule.GATHER_BROADCAST
    solver: str = "sgd"  # "sgd" (paper) | "gd" (MATLAB reference)
    lr_decay: float = 1.0
    use_kernel: bool = False  # route the gradient through the Pallas kernel


class LogisticRegressionModel(Model):
    def __init__(self, params: LogisticRegressionParameters, weights: jnp.ndarray):
        self.params = params
        self.weights = weights

    def predict_proba(self, x: jnp.ndarray) -> jnp.ndarray:
        return sigmoid(x @ self.weights)

    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        return (self.predict_proba(x) > 0.5).astype(jnp.float32)

    def loss(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        """Mean negative log likelihood."""
        logits = x @ self.weights
        return jnp.mean(jnp.logaddexp(0.0, logits) - y * logits)

    @property
    def partial(self):
        return {"weights": self.weights}


def _make_gradient(p: LogisticRegressionParameters):
    """The paper's gradient closure (or its Pallas-kernel twin), shared by
    the resident and streaming training paths."""
    if p.use_kernel:
        from repro.kernels import ops as kops

        def gradient(vec: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
            # kernel path operates on a (1, d) block
            x = vec[1:][None, :]
            y = vec[0][None]
            return kops.logreg_grad(x, y, w)
    else:
        def gradient(vec: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
            x = vec[1:]
            g = x * (sigmoid(jnp.dot(x, w)) - vec[0])
            if p.l2:
                g = g + p.l2 * w
            return g

    return gradient


# --------------------------------------------------------------------------- #
# trial-stackable form (model search; repro.tune)
# --------------------------------------------------------------------------- #
def _hyper_gradient(vec: jnp.ndarray, w: jnp.ndarray, hyper: dict) -> jnp.ndarray:
    """The paper's gradient closure with L2 as a traced hyperparameter —
    ``l2 = 0`` adds an exact zero, so regularized and unregularized
    configs share one compiled round."""
    x = vec[1:]
    return x * (sigmoid(jnp.dot(x, w)) - vec[0]) + hyper["l2"] * w


# one shared local round per local_batch_size: every trial of a stack group
# (and every fold/rung segment) reuses the same function object, so the
# runner's compiled-epoch cache hits instead of retracing
_TRIAL_ROUNDS: dict = {}


def _trial_round(local_batch_size: int):
    if local_batch_size not in _TRIAL_ROUNDS:
        _TRIAL_ROUNDS[local_batch_size] = sgd_trial_round(
            _hyper_gradient, local_batch_size)
    return _TRIAL_ROUNDS[local_batch_size]


_SCORERS: dict = {}


def _scorer(metric: str):
    """(val_table, stacked_W, schedule) -> (K,) higher-is-better scores,
    one shard-aware pass for the whole stack (repro.eval.metrics)."""
    if metric in _SCORERS:
        return _SCORERS[metric]
    from repro.eval import metrics as M

    if metric == "accuracy":
        def score(val_table, W, schedule):
            return M.accuracy(
                val_table,
                lambda X: (sigmoid(X @ W.T).T > 0.5).astype(jnp.float32),
                schedule=schedule)
    elif metric == "log_loss":
        def score(val_table, W, schedule):
            return -M.log_loss(val_table, lambda X: sigmoid(X @ W.T).T,
                               schedule=schedule)
    else:
        raise ValueError(
            f"unknown logreg metric {metric!r} (accuracy | log_loss)")
    _SCORERS[metric] = score
    return score


class LogisticRegressionAlgorithm(
    NumericAlgorithm[LogisticRegressionParameters, LogisticRegressionModel],
    StreamFitable, Searchable,
):
    """Instance-based Estimator: ``LogisticRegressionAlgorithm(
    learning_rate=0.3).fit(table) -> LogisticRegressionModel`` (the legacy
    ``train`` classmethod is an inherited deprecation shim)."""

    Parameters = LogisticRegressionParameters
    supervised = True

    def fit(self, data: MLNumericTable) -> LogisticRegressionModel:
        # the span is opened here, not in a wrapper: one more Python frame
        # under fit() doubled jax's lowering of the rounds program in the
        # benchmark's fit loop (25 -> 51 ms per fit on a v5e host; PERF.md)
        with trace.span("mli.fit"):
            p = self.params
            d = data.num_cols - 1
            gradient = _make_gradient(p)
            prox = soft_threshold(p.l1) if p.l1 else None
            w0 = jnp.zeros((d,), jnp.float32)

            if p.solver == "gd":
                opt = GradientDescent(GradientDescentParameters(
                    w_init=w0, grad=gradient, learning_rate=p.learning_rate,
                    max_iter=p.max_iter, schedule=p.schedule, prox=prox))
            else:
                opt = StochasticGradientDescent(
                    StochasticGradientDescentParameters(
                        w_init=w0, grad=gradient,
                        learning_rate=p.learning_rate, max_iter=p.max_iter,
                        schedule=p.schedule,
                        local_batch_size=p.local_batch_size, prox=prox,
                        lr_decay=p.lr_decay))
            weights = opt.apply(data, None)
            return LogisticRegressionModel(p, weights)

    def rebuild(self, partial) -> LogisticRegressionModel:
        return LogisticRegressionModel(self.params,
                                       jnp.asarray(partial["weights"]))

    def stream_state_template(self, num_cols: int) -> jnp.ndarray:
        """Shape/dtype template of the streaming-training carry for a table
        with ``num_cols`` columns (label included) — what a checkpointed
        pipeline restores into."""
        return jnp.zeros((num_cols - 1,), jnp.float32)

    @classmethod
    def trial_spec(cls, config: dict, metric: str = "accuracy"):
        """One model-search trial (see :mod:`repro.tune`): ``config``
        overrides :class:`LogisticRegressionParameters` fields, and every
        continuous hyperparameter (``learning_rate``, ``l2``, ``l1``,
        ``lr_decay``) becomes a *traced* value in the trial's hyper pytree
        — so a grid over regularization × step size stacks into one
        compiled round per K configs.  ``local_batch_size`` changes the
        compiled fold structure and therefore rides in the stack key
        (configs differing there run in separate groups).  Only the paper
        ``"sgd"`` solver is searchable (full-batch GD is a resident-table
        method with a different update structure).
        """
        import dataclasses as _dc

        from repro.tune.trials import TrialSpec

        p = _dc.replace(cls.default_parameters(), **config)
        if p.solver != "sgd":
            raise ValueError(
                f"model search supports solver='sgd' only, got {p.solver!r}")
        if p.use_kernel:
            raise ValueError("model search does not stack the Pallas-kernel "
                             "gradient (its L2 term is not hyper-traced)")
        hyper = {
            "lr": jnp.asarray(p.learning_rate, jnp.float32),
            "decay": jnp.asarray(p.lr_decay, jnp.float32),
            "l1": jnp.asarray(p.l1, jnp.float32),
            "l2": jnp.asarray(p.l2, jnp.float32),
        }

        def init(table) -> jnp.ndarray:
            return jnp.zeros((table.num_cols - 1,), jnp.float32)

        return TrialSpec(
            config=dict(config), hyper=hyper, init=init,
            local_step=_trial_round(p.local_batch_size), combine="mean",
            stack_key=("logreg", "sgd", int(p.local_batch_size)),
            score=_scorer(metric),
            finalize=lambda w: LogisticRegressionModel(p, w))

    def fit_stream(self, stream, *,
                   num_epochs: Optional[int] = None,
                   num_features: Optional[int] = None,
                   num_shards: int = 1,
                   chunks_per_epoch: Optional[int] = None,
                   checkpoint=None, resume: bool = False,
                   store=None, staleness: int = 0,
                   allow_resize: bool = False,
                   trace: Optional[list] = None
                   ) -> LogisticRegressionModel:
        """Streaming training over a :class:`repro.data.pipeline.
        BatchIterator` whose windows follow the library convention (label
        in column 0): one window per epoch, ``chunks_per_epoch`` SGD rounds
        per window, optional checkpoint/resume (see
        :meth:`repro.core.runner.DistributedRunner.run_epochs`).

        ``num_features`` may be omitted when the stream has a peekable
        ``source`` (a ``BatchIterator``); only the ``"sgd"`` solver
        streams — full-batch GD needs the whole table resident by
        definition.

        ``store`` (a :class:`repro.core.exchange.ParamStore`) selects the
        stale-synchronous multi-host lane: this host trains its own window
        locally each epoch and averages weights with its peers under the
        ``staleness`` bound.  ``allow_resize=True`` lets a resumed run
        continue on a mesh of a different world size (elastic restart).
        """
        p = self.params
        if p.solver != "sgd":
            raise ValueError(
                f"streaming supports solver='sgd' only, got {p.solver!r} "
                f"(full-batch GD is a resident-table method)")
        if num_features is None:
            if not hasattr(stream, "source"):
                raise ValueError("pass num_features= for non-peekable streams")
            num_features = stream.source(stream.step)["data"].shape[1] - 1
        gradient = _make_gradient(p)
        prox = soft_threshold(p.l1) if p.l1 else None
        opt = StochasticGradientDescent(StochasticGradientDescentParameters(
            w_init=jnp.zeros((num_features,), jnp.float32), grad=gradient,
            learning_rate=p.learning_rate, max_iter=p.max_iter,
            schedule=p.schedule, local_batch_size=p.local_batch_size,
            prox=prox, lr_decay=p.lr_decay))
        weights = opt.apply_stream(
            stream, num_epochs if num_epochs is not None else p.max_iter,
            num_shards=num_shards, chunks_per_epoch=chunks_per_epoch,
            checkpoint=checkpoint, resume=resume, store=store,
            staleness=staleness, allow_resize=allow_resize, trace=trace)
        return LogisticRegressionModel(p, weights)


#: estimator-style name for the paper's Fig. A2 terminal stage
LogisticRegression = LogisticRegressionAlgorithm
