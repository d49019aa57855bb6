"""DistributedRunner — the shared execution layer for every MLI algorithm.

The paper's claim (§III, §IV) is that one uniform contract —
``Algorithm.train(data, params) -> Model`` over a row-partitioned table —
expresses many distributed ML algorithms.  This module is the single place
that owns distributed execution:

  * **mesh + partition layout** — delegated to :mod:`repro.core.partition`,
    shared with :class:`repro.core.numeric_table.MLNumericTable` so table
    placement and execution can never disagree;
  * **one partition pass** — ``_pass`` runs a local function on every
    partition and combines the results; it is the only code that tells a
    mesh (``shard_map`` plus a collective from :mod:`repro.core.collectives`,
    with :class:`CollectiveSchedule` as a knob every algorithm exposes) from
    emulated partitions (a ``lax.map`` over the blocks of one device, one
    after another, then the algebraically-equal local reduction);
  * **one loop program** — ``_loop`` is a ``lax.scan`` over round ids, each
    round a pass, a combine and an ``update``.  The resident rounds of
    :meth:`DistributedRunner.run_rounds` are its one-chunk case and the
    streaming epochs of :meth:`DistributedRunner.run_epochs` its chunked
    case; both are jitted with the carry donated on accelerators.

Algorithms express their per-partition compute as *pure local functions*
``f(block, state, round) -> partial`` (or ``f(block, *broadcast) ->
partial`` for one-shot passes) and delegate everything else here:

    runner = DistributedRunner.for_table(table, schedule=params.schedule)
    final = runner.run_rounds(table, init, local_step, num_rounds,
                              combine="mean")

See ``docs/architecture.md`` for the data-flow diagram and ``docs/api.md``
for the full surface.

In **streaming mode** (:meth:`DistributedRunner.run_epochs`) each epoch
consumes one host window from a :class:`repro.data.pipeline.BatchIterator`
(placed on the mesh by ``shard_batch``) and runs the loop program over the
window's minibatch chunks — so training is not bounded by device memory.
Paired with :class:`CheckpointPolicy` (periodic snapshots of state + epoch +
stream position + rng through :mod:`repro.checkpoint.store`) and
:meth:`DistributedRunner.resume`, a run killed mid-flight restarts
bit-for-bit on the same mesh.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Iterator, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import partition as pt
from repro.core import trace
from repro.core.compat import shard_map
from repro.core.collectives import (
    CollectiveSchedule,
    combine_concat,
    combine_mean,
    combine_sum,
    ssp_read_round,
)

__all__ = ["CheckpointPolicy", "DistributedRunner"]

# local_step(block, state, round_index) -> per-partition partial result
LocalStep = Callable[[jnp.ndarray, Any, jnp.ndarray], Any]
# update(state, combined, round_index) -> next state (defaults to `combined`)
UpdateFn = Callable[[Any, Any, jnp.ndarray], Any]
# trial_step(block, state, round_index, hyper) -> per-partition partial for ONE
# trial; the stacked entry points vmap it over the trial axis
TrialStep = Callable[[jnp.ndarray, Any, jnp.ndarray, Any], Any]
# trial_update(state, combined, round_index, hyper) -> next state for ONE trial
TrialUpdateFn = Callable[[Any, Any, jnp.ndarray, Any], Any]

_COMBINERS = {
    "mean": combine_mean,
    "sum": combine_sum,
    "concat": combine_concat,
}


@dataclasses.dataclass
class CheckpointPolicy:
    """When and where the streaming loop snapshots its state.

    Every ``every_epochs`` completed epochs, :meth:`DistributedRunner.
    run_epochs` writes one atomic checkpoint through
    :mod:`repro.checkpoint.store` carrying the state pytree **and** the
    host-side loop counters (epoch, stream step, rng key, chunk layout,
    schedule) — everything :meth:`DistributedRunner.resume` needs to
    restart the run bit-for-bit.  ``keep`` bounds disk usage by pruning all
    but the newest ``keep`` snapshots after each publish.

    ``extra_state`` (a pytree of arrays) and ``extra_metadata`` (a
    JSON-able dict) ride in every snapshot *alongside* the training carry —
    one atomic file, so a composite artifact (a fitted pipeline's
    featurizer statistics + model state + stream position) can never be
    torn apart by a crash.  On resume the restored extra tree replaces
    ``extra_state`` in place, so the caller reads the snapshot's values
    back off the policy.
    """

    ckpt_dir: str
    every_epochs: int = 1
    keep: Optional[int] = None
    extra_state: Any = None
    extra_metadata: Optional[dict] = None

    def __post_init__(self) -> None:
        if self.every_epochs < 1:
            raise ValueError(f"every_epochs must be >= 1, got {self.every_epochs}")
        if self.keep is not None and self.keep < 1:
            raise ValueError(f"keep must be >= 1, got {self.keep}")


def _default_update(state: Any, combined: Any, r: jnp.ndarray) -> Any:
    """The default ``update``: the combined value becomes the next state.

    A module-level function (not a per-call lambda) so repeated
    ``run_epochs`` calls with the default update share one jit cache entry.
    """
    return combined


def _mask_tree(active: jnp.ndarray, new: Any, old: Any) -> Any:
    """Per-leaf ``where`` with the (K,) trial mask broadcast over each
    leaf's trailing dims — stopped trials keep their frozen state."""
    def leaf(n, o):
        m = active.reshape(active.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)

    return jax.tree.map(leaf, new, old)


def _stacked_callback_shim(cb: Callable) -> Callable:
    """Adapt a trial-level callback to the stacked carry: the env the
    callback sees carries the (K, …) trial tree / hyper / host active
    mask instead of the raw carry dict, and any ``state``/``hyper``/
    ``active`` swap it returns is folded back into the carry.  Dispatch
    attributes (``order``, ``before_epoch``) are preserved."""
    def shim(env):
        carry = env.state
        tenv = dataclasses.replace(env, state=carry["trial"],
                                   hyper=carry["hyper"],
                                   active=np.asarray(carry["active"]))
        out = cb(tenv)
        if not out:
            return None
        new = dict(carry)
        if "state" in out:
            new["trial"] = out["state"]
        if "hyper" in out:
            new["hyper"] = out["hyper"]
        if "active" in out:
            new["active"] = jnp.asarray(out["active"])
        return {"state": new}

    shim.order = getattr(cb, "order", 10)
    shim.before_epoch = getattr(cb, "before_epoch", False)
    return shim


def _map_partitions(fn: Callable, blocks: Any) -> Any:
    """Run ``fn`` on each partition of ``blocks`` (every leaf has a leading
    ``(num_shards, ...)`` axis), one partition after another, and stack the
    results ``(num_shards, ...)``.

    The emulated form of the mesh's one-partition-per-chip pass: a
    ``lax.map``, so ``fn`` traces and compiles once, with no partition
    batch dimension, as on one chip of the mesh.  A ``vmap`` here would
    give every op of ``fn`` that dimension, and XLA then transposes each
    minibatch the local step reads.
    """
    return jax.lax.map(fn, blocks)


def _emulated_combine(stacked: Any, combine: str) -> Any:
    """Combine a (shards, ...) stacked tree without a mesh — the
    algebraically-equal local form of each collective."""
    if combine == "mean":
        return jax.tree.map(lambda x: jnp.mean(x, axis=0), stacked)
    if combine == "sum":
        return jax.tree.map(lambda x: jnp.sum(x, axis=0), stacked)
    if combine == "concat":
        return jax.tree.map(pt.unpartition_rows, stacked)
    raise ValueError(f"unknown combine {combine!r}")


@dataclasses.dataclass
class DistributedRunner:
    """Owns mesh construction, data partitioning, and the per-round combine.

    Parameters
    ----------
    mesh:
        Device mesh, or ``None`` for emulated partitions on one device.
    num_shards:
        Partition count in emulated mode (ignored when a mesh is given —
        then it is derived from the data-axis sizes).
    data_axes:
        Mesh axes carrying the row partitions; inferred from the mesh when
        omitted (``("pod", "data")`` subset, outermost first).
    schedule:
        The :class:`CollectiveSchedule` used for every global combine.
    donate:
        Donate the carry buffers of the round loop to the jitted scan so
        parameter memory is reused across rounds.  ``None`` (default) turns
        donation on exactly when the backend supports it (not CPU, where XLA
        would warn and ignore it).
    """

    mesh: Optional[Mesh] = None
    num_shards: int = 1
    data_axes: Optional[Tuple[str, ...]] = None
    schedule: Union[str, CollectiveSchedule] = CollectiveSchedule.ALLREDUCE
    donate: Optional[bool] = None

    def __post_init__(self) -> None:
        self.schedule = CollectiveSchedule.parse(self.schedule)
        if self.mesh is not None:
            if self.data_axes is None:
                self.data_axes = pt.infer_data_axes(self.mesh)
            self.num_shards = pt.num_data_shards(self.mesh, self.data_axes)
        else:
            self.data_axes = ()
        if self.donate is None:
            self.donate = jax.default_backend() != "cpu"
        # jitted one-epoch functions, keyed by (local_step, update, combine,
        # chunks): repeated run_epochs calls with the SAME function objects
        # (the tune layer's rung loop, resume continuations) reuse the
        # compiled epoch instead of retracing.  Callers that build a fresh
        # closure per call simply miss; the cache is capped so a long-lived
        # runner fed per-call closures cannot leak dead executables.
        self._epoch_cache: dict = {}
        self._epoch_cache_max = 16

    # ------------------------------------------------------------------ #
    # construction
    # ------------------------------------------------------------------ #
    @classmethod
    def for_table(cls, table: Any,
                  schedule: Union[str, CollectiveSchedule] = CollectiveSchedule.ALLREDUCE,
                  donate: Optional[bool] = None) -> "DistributedRunner":
        """Build a runner matching a table's mesh / partition layout.

        Accepts anything with ``mesh``, ``num_shards`` and (when meshed)
        ``data_axes`` attributes — i.e. an :class:`MLNumericTable`."""
        return cls(mesh=table.mesh, num_shards=table.num_shards,
                   data_axes=getattr(table, "data_axes", None) or None,
                   schedule=schedule, donate=donate)

    # ------------------------------------------------------------------ #
    # primitive: one pass over partitions (trace-safe)
    # ------------------------------------------------------------------ #
    def _pass(self, fn: Callable, data: jnp.ndarray,
              broadcast: Tuple[Any, ...], combine: Optional[str]) -> Any:
        """Run ``fn(block, *broadcast)`` on every partition of ``data``.

        The only place the runner tells a mesh from emulated partitions.
        On a mesh, a ``shard_map`` hands each chip its own rows and the
        combine is a collective under the configured schedule.  Without
        one, the rows are split into ``(num_shards, rows, ...)`` blocks and
        ``_map_partitions`` runs ``fn`` on one block after another, so
        ``fn`` compiles as it does on one chip of the mesh; the combine is
        the algebraically-equal local reduction.  ``combine=None`` returns
        the per-partition results stacked on a leading ``(num_shards,
        ...)`` axis.
        """
        if self.mesh is not None:
            axes = self.data_axes

            def spmd(block: jnp.ndarray, *args: Any) -> Any:
                out = fn(block, *args)
                if combine is None:
                    return jax.tree.map(lambda x: x[None], out)
                return _COMBINERS[combine](out, axes, self.schedule)

            return shard_map(
                spmd,
                mesh=self.mesh,
                in_specs=(pt.data_spec(axes),) + tuple(P() for _ in broadcast),
                out_specs=P(axes) if combine is None else P(),
            )(data, *broadcast)

        blocks = pt.partition_rows(data, self.num_shards)
        stacked = _map_partitions(lambda block: fn(block, *broadcast), blocks)
        if combine is None:
            return stacked
        return _emulated_combine(stacked, combine)

    def partition_apply(self, data: jnp.ndarray, fn: Callable,
                        broadcast: Sequence[Any] = (),
                        combine: Optional[str] = None) -> Any:
        """Run ``fn(block, *broadcast)`` on every partition of ``data``.

        ``combine=None`` returns the stacked per-partition results with a
        leading ``(num_shards, ...)`` axis; ``"mean" | "sum" | "concat"``
        combines them across partitions with the configured schedule.
        Callable inside ``jax.jit`` — algorithms with bespoke outer loops
        (ALS) build on this directly.  ``fn`` is traced, never run on
        concrete blocks.
        """
        return self._pass(fn, data, tuple(broadcast), combine)

    # ------------------------------------------------------------------ #
    # one-shot sufficient-statistics pass
    # ------------------------------------------------------------------ #
    def run_once(self, table: Any, local_fn: Callable, *broadcast: Any,
                 combine: str = "sum") -> Any:
        """One combined pass: ``local_fn(block, *broadcast)`` per partition,
        then one global combine.  The pattern of the closed-form algorithms
        (PCA moments, naive Bayes counts)."""
        return self.partition_apply(table.data, local_fn, broadcast, combine)

    # ------------------------------------------------------------------ #
    # the paper's iterate-and-combine loop
    # ------------------------------------------------------------------ #
    def _carry(self, state: Any) -> Any:
        """The carry a compiled loop starts from.

        Replicated onto the mesh (kept as is when emulated or already
        placed): segmented callers alternate host-built carries (first
        segment) with device outputs of the previous segment, and without
        one canonical input sharding the jitted loop compiles TWICE for the
        same shapes — the exact miss ``repro.analysis.assert_no_retrace``
        flags on the rung loop.  Under an outer trace the caller governs
        placement; a staged ``device_put`` would read as a per-step
        transfer in the jaxpr audit.  When the loop donates its carry, the
        carry is a private copy, never the caller's buffer (an initial
        state is typically a params field the caller reuses)."""
        if self.mesh is not None and jax.core.trace_ctx.is_top_level():
            sharding = jax.sharding.NamedSharding(self.mesh, P())

            def place(x):
                if getattr(x, "sharding", None) == sharding:
                    return x
                return jax.device_put(x, sharding)

            state = jax.tree.map(place, state)
        return jax.tree.map(jnp.copy, state) if self.donate else state

    def _loop(self, local_step: LocalStep, upd: UpdateFn, combine: str,
              chunks: int) -> Callable:
        """The one loop program, ``(state, data, rounds) -> state``: a
        ``lax.scan`` over the round ids in ``rounds``.  Round ``r`` runs
        ``local_step`` on chunk ``r % chunks`` of every partition's rows
        (the whole partition when ``chunks`` is 1), combines the partials
        in one pass and applies ``update``.  Round ids are global (epoch ·
        chunks + chunk), so round-indexed local steps (lr decay, rotating
        slices) see a monotone counter across epochs."""
        def loop(state, data, rounds):
            def round_body(state, r):
                def local(block, state):
                    if chunks > 1:
                        cr = block.shape[0] // chunks
                        block = jax.lax.dynamic_slice_in_dim(
                            block, (r % chunks) * cr, cr, axis=0)
                    return local_step(block, state, r)

                combined = self._pass(local, data, (state,), combine)
                return upd(state, combined, r), None

            final, _ = jax.lax.scan(round_body, state, rounds)
            return final

        return loop

    def run_rounds(self, table: Any, init_state: Any, local_step: LocalStep,
                   num_rounds: int, *, combine: str = "mean",
                   update: Optional[UpdateFn] = None,
                   start_round: int = 0) -> Any:
        """Run ``num_rounds`` of: per-partition ``local_step(block, state,
        r)`` → global combine (configured schedule) → ``update(state,
        combined, r)``.

        This is the paper's main loop (Fig. A4 middle: localSGD +
        avgWeights) generalized: parameter-averaging methods pass
        ``combine="mean"`` and no ``update``; sufficient-statistics methods
        (k-means) pass ``combine="sum"`` and an ``update`` that rebuilds the
        state.  The whole loop compiles to one jitted ``lax.scan``; the
        state carry is donated when the backend supports it.

        ``start_round`` offsets the round indices ``local_step`` sees —
        callers that split one logical run into segments (the tune layer's
        early-stopping rungs) keep lr decay and rotating slices monotone
        across segments.
        """
        with trace.span("mli.runner.prepare"):
            run = self.rounds_fn(local_step, num_rounds, combine=combine,
                                 update=update, start_round=start_round)
            init_state = self._carry(init_state)
        with trace.span("mli.runner.launch"):
            t0 = time.perf_counter()
            final = run(init_state, table.data)
            launch_s = time.perf_counter() - t0
        # staged into an outer program (make_jaxpr, an outer jit), the call
        # above launched nothing
        if jax.core.trace_ctx.is_top_level():
            trace.count("runner.launches")
            trace.count("runner.rounds", num_rounds)
            trace.count("runner.launch_s", launch_s)
            if self.mesh is None:
                trace.count("runner.partition_passes",
                            self.num_shards * num_rounds)
        return final

    def rounds_fn(self, local_step: LocalStep, num_rounds: int, *,
                  combine: str = "mean", update: Optional[UpdateFn] = None,
                  start_round: int = 0) -> Callable:
        """The jitted program :meth:`run_rounds` drives, ``(state0, data)
        -> final state``: the loop program over the whole table as one
        chunk, its round ids a constant, with the state carry donated when
        ``donate`` is on.  The table is an argument, never a closed-over
        constant, so the compiled program does not embed it; public so a
        caller can lower and compile it from shapes alone.  The function
        is named ``mli_rounds``: its program is ``jit_mli_rounds`` in a
        profile."""
        loop = self._loop(local_step, update or _default_update, combine, 1)
        rounds = jnp.asarray(np.arange(start_round,
                                       start_round + num_rounds,
                                       dtype=np.int32))

        @partial(jax.jit, donate_argnums=(0,) if self.donate else ())
        def mli_rounds(state0, data):
            return loop(state0, data, rounds)

        return mli_rounds

    # ------------------------------------------------------------------ #
    # streaming mode: epochs over minibatch windows (beyond the paper)
    # ------------------------------------------------------------------ #
    def _check_window(self, window: jnp.ndarray, chunks_per_epoch: int) -> None:
        pt.check_rows_divisible(window.shape[0], self.num_shards,
                                what="stream partitions")
        per_shard = window.shape[0] // self.num_shards
        if per_shard % chunks_per_epoch != 0:
            raise ValueError(
                f"rows-per-shard {per_shard} must divide into "
                f"chunks_per_epoch={chunks_per_epoch}")

    def epoch_fn(self, local_step: LocalStep,
                 update: Optional[UpdateFn] = None, *,
                 combine: str = "mean", chunks_per_epoch: int = 1) -> Callable:
        """The cached jitted one-epoch function ``(state, window, rounds)
        -> state`` that :meth:`run_epochs` drives.

        Public so callers can warm it ahead of a latency-sensitive stream
        and so :mod:`repro.analysis` can audit the exact jaxpr the epoch
        loop executes (same cache, same donation flags — not a
        reconstruction)."""
        upd: UpdateFn = update or _default_update
        chunks = int(chunks_per_epoch)
        if chunks < 1:
            raise ValueError(f"chunks_per_epoch must be >= 1, got {chunks}")
        cache_key = (local_step, upd, combine, chunks)
        if cache_key not in self._epoch_cache:
            loop = self._loop(local_step, upd, combine, chunks)

            @partial(jax.jit, donate_argnums=(0,) if self.donate else ())
            def epoch(state, window, rounds):
                return loop(state, window, rounds)

            self._cache_put(cache_key, epoch)
        return self._epoch_cache[cache_key]

    def run_epochs(self, stream: Iterator, init_state: Any,
                   local_step: LocalStep, num_epochs: int, *,
                   combine: str = "mean", update: Optional[UpdateFn] = None,
                   chunks_per_epoch: int = 1,
                   checkpoint: Optional[CheckpointPolicy] = None,
                   rng: Optional[jnp.ndarray] = None,
                   start_epoch: int = 0,
                   callbacks: Sequence[Callable] = (),
                   eval_fn: Optional[Callable] = None) -> Any:
        """Streaming variant of :meth:`run_rounds` for data larger than
        device memory: each epoch pulls ONE window of rows from ``stream``
        (a :class:`repro.data.pipeline.BatchIterator` yielding ``{"data":
        (rows, features)}`` host batches, already mesh-placed by
        ``shard_batch``) and runs ``chunks_per_epoch`` rounds of
        local-step → combine → update over it as a single jitted
        ``lax.scan`` with the state carry donated.  Round ``r`` of epoch
        ``e`` sees the window's ``r % chunks_per_epoch``-th row chunk and
        the global round index ``e * chunks_per_epoch + r``.

        With a :class:`CheckpointPolicy`, every ``every_epochs`` epochs the
        ``(state, epoch, stream.step, rng)`` tuple is snapshotted
        atomically via :mod:`repro.checkpoint.store`; :meth:`resume`
        restarts from the newest snapshot bit-for-bit.  ``rng`` is an
        optional uint32 key carried for stochastic pipelines (fold per
        epoch with ``jax.random.fold_in(rng, epoch)``); it rides in the
        checkpoint so a resumed run re-derives identical per-epoch keys.

        ``callbacks`` are host-side hooks fired *between* compiled epochs
        (the :mod:`repro.tune.callback` protocol): before-epoch callbacks
        may return ``{"state": ...}`` swaps the next epoch trains on
        (hyper schedules), after-epoch callbacks see ``eval_fn(state,
        epoch) -> [EvalEntry, ...]`` results and may raise
        :class:`repro.tune.callback.EarlyStopException` to end the loop —
        the tail checkpoint is still written, so an early-stopped run
        resumes/inspects like a completed one.  Hooks never change the
        compiled round structure.
        """
        if num_epochs < start_epoch:
            raise ValueError(f"num_epochs {num_epochs} < start_epoch {start_epoch}")
        chunks = int(chunks_per_epoch)
        epoch_fn = self.epoch_fn(local_step, update, combine=combine,
                                 chunks_per_epoch=chunks)

        before = after = ()
        if callbacks:
            from repro.tune.callback import CallbackEnv, split_callbacks
            before, after = split_callbacks(callbacks)

        state = self._carry(init_state)

        last_saved = None
        rows = None
        done = num_epochs
        for e in range(start_epoch, num_epochs):
            stopped = False
            if before:
                env = CallbackEnv(epoch=e, begin_epoch=start_epoch,
                                  end_epoch=num_epochs, round=e * chunks,
                                  state=state)
                state, stop = self._fire(before, env, state)
                if stop:
                    done = e
                    break
            batch = next(stream)
            window = batch["data"] if isinstance(batch, dict) else batch
            self._check_window(window, chunks)
            rows = int(window.shape[0])
            # numpy-built + device_put: jnp.arange(start, ...) compiles a
            # different tiny program for zero vs nonzero starts, so the
            # first post-resume/rung epoch would trip the retrace sentinel
            rounds = jnp.asarray(np.arange(e * chunks, (e + 1) * chunks,
                                           dtype=np.int32))
            state = epoch_fn(state, window, rounds)
            done = e + 1
            if after:
                evals = tuple(eval_fn(state, done)) if eval_fn else ()
                env = CallbackEnv(epoch=done, begin_epoch=start_epoch,
                                  end_epoch=num_epochs, round=done * chunks,
                                  state=state, evals=evals)
                state, stopped = self._fire(after, env, state)
            if checkpoint is not None and done % checkpoint.every_epochs == 0:
                self._save_snapshot(checkpoint, stream, state, done, chunks,
                                    rng, rows=rows)
                last_saved = done
            if stopped:
                break
        if checkpoint is not None and last_saved != done:
            self._save_snapshot(checkpoint, stream, state, done, chunks,
                                rng, rows=rows)
        return state

    def _fire(self, hooks: Sequence[Callable], env: Any,
              state: Any) -> Tuple[Any, bool]:
        """Fire host callbacks between two epochs of :meth:`run_epochs`.
        Returns the state the next epoch trains on (a callback may swap
        it) and whether a callback asked the loop to stop."""
        from repro.tune.callback import EarlyStopException, fire_callbacks

        try:
            swaps = fire_callbacks(hooks, env)
        except EarlyStopException:
            return state, True
        if set(swaps) - {"state"}:
            raise ValueError(
                f"run_epochs carries only 'state' — a callback "
                f"returned {sorted(set(swaps) - {'state'})} (hyper/"
                f"active swaps need the stacked loop)")
        if "state" in swaps:
            # swapped states come from host callbacks: re-place them so the
            # compiled epoch's input sharding never drifts
            state = self._carry(swaps["state"])
        return state, False

    def _save_snapshot(self, policy: CheckpointPolicy, stream: Any, state: Any,
                       epoch: int, chunks: int, rng: Optional[jnp.ndarray], *,
                       rows: Optional[int] = None,
                       extra_meta: Optional[dict] = None) -> None:
        from repro.checkpoint.store import save_checkpoint

        if jax.process_count() > 1 and jax.process_index() != 0:
            # one writer per global mesh: every host computes the identical
            # replicated state, process 0 persists it (shared filesystem);
            # the SSP exchange lane never reaches here multi-process — its
            # hosts are independent single-process programs with their own
            # checkpoint dirs.  The other hosts still wait for the state, so
            # none runs ahead of the writer's snapshot: a host lost at the
            # next window leaves this snapshot behind for the restart.
            jax.block_until_ready(state)
            return
        stream_step = getattr(stream, "step", None)
        if stream_step is None:
            raise TypeError(
                "checkpointing requires a stream exposing its position as "
                ".step (a BatchIterator) — resume could not replay an "
                "unpositioned stream")
        meta = {
            "epoch": epoch,
            "stream_step": int(stream_step),
            "rng": None if rng is None else np.asarray(rng).tolist(),
            "chunks_per_epoch": chunks,
            "schedule": self.schedule.value,
            "num_shards": self.num_shards,
            "num_hosts": jax.process_count(),
            "rows_per_epoch": rows,
            "every_epochs": policy.every_epochs,
            "keep": policy.keep,
            "wrapped": policy.extra_state is not None,
        }
        if extra_meta:
            meta.update(extra_meta)
        tree = state
        if policy.extra_state is not None:
            # one atomic unit: the training carry plus the caller's extra
            # state (e.g. a pipeline's fitted featurizer statistics)
            tree = {"state": state, "extra": policy.extra_state}
        if policy.extra_metadata is not None:
            meta["extra"] = policy.extra_metadata
        save_checkpoint(policy.ckpt_dir, epoch, tree, metadata=meta,
                        keep=policy.keep)

    def _restore(self, ckpt_dir: str, stream: Any, template: Any,
                 step: Optional[int], checkpoint: Optional[CheckpointPolicy],
                 writer: str) -> Tuple[Any, dict, Any, int,
                                       Optional[CheckpointPolicy]]:
        """The restore both resume paths share: the snapshot's tree and
        metadata, the stream sought to its position, the rng key, the
        epoch, and the checkpoint policy (rebuilt from the metadata when
        the caller passes none).  ``writer`` names the loop that writes
        such snapshots, for the error a snapshot without metadata raises."""
        from repro.checkpoint.store import restore_with_metadata

        state, ck_step, meta = restore_with_metadata(ckpt_dir, template, step)
        if meta is None:
            raise ValueError(
                f"checkpoint step {ck_step} under {ckpt_dir} carries no "
                f"resume metadata — was it written by {writer}?")
        if not hasattr(stream, "seek"):
            raise TypeError("resume requires a seekable stream "
                            "(BatchIterator or anything with .seek(step))")
        stream.seek(meta["stream_step"])
        rng = (jnp.asarray(meta["rng"], jnp.uint32)
               if meta.get("rng") is not None else None)
        if checkpoint is None and meta.get("every_epochs"):
            checkpoint = CheckpointPolicy(ckpt_dir, meta["every_epochs"],
                                          meta.get("keep"))
        return state, meta, rng, int(meta["epoch"]), checkpoint

    def resume(self, ckpt_dir: str, stream: Any, init_state: Any,
               local_step: LocalStep, num_epochs: int, *,
               combine: str = "mean", update: Optional[UpdateFn] = None,
               chunks_per_epoch: Optional[int] = None,
               checkpoint: Optional[CheckpointPolicy] = None,
               step: Optional[int] = None,
               allow_resize: bool = False) -> Any:
        """Restart a killed :meth:`run_epochs` run from its newest (or
        ``step``-selected) checkpoint and continue to ``num_epochs``.

        ``init_state`` is only the structure template for the restore — its
        values are replaced by the snapshot.  The stream is fast-forwarded
        with ``seek`` to the checkpointed position, the rng key restored,
        and the chunk layout / schedule / shard count cross-checked against
        the snapshot so a mismatched relaunch fails loudly instead of
        silently diverging.  On the same mesh the resumed run replays the
        identical compiled computation, so the final state matches an
        uninterrupted run bit-for-bit (asserted in
        ``tests/test_streaming_resume.py``).

        ``allow_resize=True`` is the elastic path: the shard-count
        cross-check is replaced by a :func:`repro.core.partition.plan_resize`
        validation (rows must still split evenly over the new layout), so a
        surviving mesh of a different world size can pick the run up from
        the same snapshot — live migration as checkpoint-and-restart.  The
        state pytree itself is layout-free (combines produce replicated
        trees), so only the stream's row partitioning changes.
        """
        from repro.checkpoint.store import load_metadata

        peek = load_metadata(ckpt_dir, step) or {}
        wrapped = bool(peek.get("wrapped"))
        template = init_state
        if wrapped:
            if checkpoint is None or checkpoint.extra_state is None:
                raise ValueError(
                    f"checkpoint under {ckpt_dir} carries extra (pipeline) "
                    f"state — resume needs the CheckpointPolicy with an "
                    f"extra_state template to restore it atomically")
            template = {"state": init_state, "extra": checkpoint.extra_state}
        state, meta, rng, epoch, checkpoint = self._restore(
            ckpt_dir, stream, template, step, checkpoint, "run_epochs")
        if wrapped:
            # hand the restored extra tree back through the policy (and
            # keep re-saving it with every later snapshot)
            checkpoint.extra_state = state["extra"]
            state = state["state"]
        for name, have in (("schedule", self.schedule.value),
                           ("num_shards", self.num_shards)):
            want = meta.get(name)
            if want is None or want == have:
                continue
            if name == "num_shards" and allow_resize:
                rows = meta.get("rows_per_epoch")
                if rows:
                    # validates the new layout and quantifies the motion;
                    # raises before any epoch runs when the rows cannot
                    # split evenly over the surviving shards
                    pt.plan_resize(int(rows), int(want), int(have))
                continue
            raise ValueError(
                f"cannot resume: checkpoint was written with "
                f"{name}={want!r} but this runner has {name}={have!r}"
                + ("" if name != "num_shards" else
                   " (pass allow_resize=True to repartition onto the "
                   "surviving mesh)"))
        chunks = int(meta.get("chunks_per_epoch", 1))
        if chunks_per_epoch is not None and chunks_per_epoch != chunks:
            raise ValueError(
                f"cannot resume: checkpoint used chunks_per_epoch={chunks}, "
                f"got {chunks_per_epoch}")
        if epoch >= num_epochs:
            return state
        return self.run_epochs(stream, state, local_step, num_epochs,
                               combine=combine, update=update,
                               chunks_per_epoch=chunks, checkpoint=checkpoint,
                               rng=rng, start_epoch=epoch)

    # ------------------------------------------------------------------ #
    # stale-synchronous parallel lane: independent hosts, bounded clocks
    # ------------------------------------------------------------------ #
    def _ssp_merge(self, entries, combine: str) -> Any:
        """Combine ``[(host_id, tree), ...]`` across hosts in host-id order.

        Canonical ordering is the determinism contract: every participant
        stacks the same trees in the same order and reduces along the new
        axis, so the arithmetic (and therefore the bits) is identical on
        every host and in the in-process reference simulator the chaos
        tests compare against.
        """
        trees = [t for _, t in sorted(entries, key=lambda kv: kv[0])]
        if combine == "mean":
            return jax.tree.map(
                lambda *xs: jnp.mean(jnp.stack(xs, axis=0), axis=0), *trees)
        if combine == "sum":
            return jax.tree.map(
                lambda *xs: jnp.sum(jnp.stack(xs, axis=0), axis=0), *trees)
        raise ValueError(f"SSP lane supports combine='mean'|'sum', "
                         f"got {combine!r}")

    def run_epochs_ssp(self, stream: Iterator, init_state: Any,
                       local_step: LocalStep, num_epochs: int, *,
                       store: Any, staleness: int = 0,
                       combine: str = "mean",
                       update: Optional[UpdateFn] = None,
                       chunks_per_epoch: int = 1,
                       checkpoint: Optional[CheckpointPolicy] = None,
                       rng: Optional[jnp.ndarray] = None,
                       start_epoch: int = 0,
                       trace: Optional[list] = None) -> Any:
        """Streaming epochs with **stale-synchronous** cross-host exchange.

        The second execution mode the multi-host work adds: hosts are
        *independent* single-process programs (each with its own local mesh
        or emulated partitions) that exchange through a shared
        :class:`repro.core.exchange.ParamStore` instead of lock-step
        collectives.  Each exchange round (one epoch) host ``h``:

          1. computes its local contribution for round ``e`` and
             **publishes** it (atomic, crash-safe — the same file machinery
             as checkpoints);
          2. **waits** until every live peer has published round
             ``>= e - staleness`` — the SSP bound: a host may run at most
             ``staleness`` rounds ahead of the slowest peer;
          3. **reads** each peer's freshest publication capped at its own
             round (:func:`repro.core.collectives.ssp_read_round`) and
             merges in canonical host-id order (:meth:`_ssp_merge`).

        ``staleness=0`` degenerates to lock-step BSP *by construction*:
        step 2 blocks until every peer published round ``e`` exactly, step
        3 reads exactly round ``e`` from everyone — every host merges the
        identical entry set in the identical order, bit-for-bit equal to
        the sequential reference simulator (asserted in
        ``tests/chaos/``).  With ``staleness=s>0`` a straggler no longer
        stalls the cohort: fast hosts keep computing on contributions up
        to ``s`` rounds stale (the Petuum trade-off the benchmark
        ``benchmarks/elastic_ssp.py`` quantifies).

        Two algorithm shapes map onto the lane through ``combine``:

        * ``"mean"`` (parameter averaging, e.g. logistic SGD): the local
          contribution is the host's **post-epoch state** (a local
          ``chunks_per_epoch``-round epoch via the normal jitted epoch
          scan); the merge averages states across hosts — local SGD with
          bounded-staleness averaging.
        * ``"sum"`` + ``update`` (sufficient statistics, e.g. k-means):
          the local contribution is the host's **statistics tree** for the
          round; the merge sums them and ``update`` rebuilds the state.
          Requires ``chunks_per_epoch == 1`` so rounds and exchange rounds
          coincide.

        Departed peers (``store.mark_left()``, the ``drop`` chaos action)
        are excluded as soon as their last in-bound contribution ages out;
        the cohort shrinks without restarting — in-place elastic resize
        for the exchange lane.  ``trace``, when given a list, receives one
        ``{"epoch", "reads", "wait_seconds"}`` record per exchange round —
        the raw material of the staleness-bound assertions in
        ``tests/chaos/test_ssp_property.py``.

        Checkpoints are **per host** (each host snapshots its own state to
        its own directory, with ``staleness`` and the store's world size in
        the metadata); :meth:`resume_ssp` restarts a killed host against
        the *same* store — surviving publications are still on disk, so
        the cohort only blocks for the restart gap, bounded by the store
        timeout.
        """
        import time as _time

        if num_epochs < start_epoch:
            raise ValueError(f"num_epochs {num_epochs} < start_epoch {start_epoch}")
        staleness = int(staleness)
        if staleness < 0:
            raise ValueError(f"staleness must be >= 0, got {staleness}")
        chunks = int(chunks_per_epoch)
        if chunks < 1:
            raise ValueError(f"chunks_per_epoch must be >= 1, got {chunks}")
        upd: UpdateFn = update or _default_update
        stats_lane = combine == "sum"
        if stats_lane:
            if update is None:
                raise ValueError(
                    "SSP combine='sum' is the sufficient-statistics lane — "
                    "it needs an update(state, merged_stats, r) to rebuild "
                    "the state from the cross-host sum")
            if chunks != 1:
                raise ValueError(
                    "SSP combine='sum' requires chunks_per_epoch=1 so "
                    "exchange rounds and algorithm rounds coincide")
        elif combine != "mean":
            raise ValueError(f"SSP lane supports combine='mean'|'sum', "
                             f"got {combine!r}")

        epoch_fn = None if stats_lane else self.epoch_fn(
            local_step, update, combine=combine, chunks_per_epoch=chunks)

        state = init_state
        if self.donate and not stats_lane:
            state = jax.tree.map(jnp.copy, state)

        rows = None
        last_saved = None
        for e in range(start_epoch, num_epochs):
            batch = next(stream)
            window = batch["data"] if isinstance(batch, dict) else batch
            self._check_window(window, chunks)
            rows = int(window.shape[0])
            if stats_lane:
                r = jnp.asarray(e, jnp.int32)
                mine = self.partition_apply(
                    window, local_step, broadcast=(state, r), combine="sum")
            else:
                rounds = jnp.asarray(np.arange(e * chunks, (e + 1) * chunks,
                                               dtype=np.int32))
                mine = epoch_fn(state, window, rounds)
            mine = jax.tree.map(np.asarray, jax.device_get(mine))
            store.publish(e, mine)

            entries = [(store.host_id, mine)]
            reads = {}
            waited = 0.0
            for p in store.peers():
                t0 = _time.monotonic()
                clock = store.wait_clock(p, e - staleness + 1)
                waited += _time.monotonic() - t0
                if clock <= e - staleness:
                    # departed peer whose last word is out of bound: it has
                    # aged out of the cohort (in-place shrink)
                    continue
                tau = ssp_read_round(e, clock, staleness)
                got = store.read_at_most(p, tau, mine)
                if got is None:
                    continue
                entries.append((p, got[0]))
                reads[p] = got[1]
            merged = self._ssp_merge(entries, combine)
            state = upd(state, merged, jnp.asarray(e, jnp.int32)) \
                if stats_lane else merged
            if trace is not None:
                trace.append({"epoch": e, "reads": reads,
                              "wait_seconds": waited})
            if checkpoint is not None and (e + 1) % checkpoint.every_epochs == 0:
                self._save_snapshot(
                    checkpoint, stream, state, e + 1, chunks, rng, rows=rows,
                    extra_meta={"staleness": staleness,
                                "ssp_hosts": store.num_hosts,
                                "ssp_host_id": store.host_id})
                last_saved = e + 1
        if checkpoint is not None and last_saved != num_epochs:
            self._save_snapshot(
                checkpoint, stream, state, num_epochs, chunks, rng, rows=rows,
                extra_meta={"staleness": staleness,
                            "ssp_hosts": store.num_hosts,
                            "ssp_host_id": store.host_id})
        return state

    def resume_ssp(self, ckpt_dir: str, stream: Any, init_state: Any,
                   local_step: LocalStep, num_epochs: int, *,
                   store: Any, staleness: Optional[int] = None,
                   combine: str = "mean", update: Optional[UpdateFn] = None,
                   checkpoint: Optional[CheckpointPolicy] = None,
                   step: Optional[int] = None,
                   trace: Optional[list] = None) -> Any:
        """Restart one killed SSP host from its own checkpoint and rejoin
        the cohort on the *same* store.

        Peers' publications survive a host's death on disk, so the
        restarted host replays from its snapshot (identical bits — same
        mesh, same compiled epoch) and re-publishes the rounds it had
        already shared; peers consumed the originals, the replays are
        byte-identical, and the clocks re-converge.  ``staleness`` defaults
        to the checkpointed value.
        """
        state, meta, rng, epoch, checkpoint = self._restore(
            ckpt_dir, stream, init_state, step, checkpoint, "run_epochs_ssp")
        if staleness is None:
            staleness = int(meta.get("staleness", 0))
        chunks = int(meta.get("chunks_per_epoch", 1))
        if epoch >= num_epochs:
            return state
        return self.run_epochs_ssp(
            stream, state, local_step, num_epochs, store=store,
            staleness=staleness, combine=combine, update=update,
            chunks_per_epoch=chunks, checkpoint=checkpoint, rng=rng,
            start_epoch=epoch, trace=trace)

    # ------------------------------------------------------------------ #
    # device-stacked trials: K models per round (model search; repro.tune)
    # ------------------------------------------------------------------ #
    def _stacked_carry(self, trial_states: Any, trial_hyper: Any,
                       active: Optional[jnp.ndarray],
                       offsets: Optional[jnp.ndarray] = None) -> dict:
        """Assemble the carry of a stacked run: ``trial`` (every leaf has a
        leading (K, …) trial axis), ``hyper`` (per-trial scalar
        hyperparameters, leading (K,)), ``active`` (the (K,) bool mask
        early stopping freezes trials with), and ``offset`` (per-trial
        round offsets: lane ``j`` sees trial-local round ``r - offset[j]``,
        so a trial backfilled into a freed slot mid-search trains on the
        same round indices — lr decay, rotating slices — as a solo run
        from round 0)."""
        leaves = jax.tree.leaves(trial_states)
        if not leaves:
            raise ValueError("trial_states must have at least one array leaf")
        k = leaves[0].shape[0]
        for leaf in leaves + jax.tree.leaves(trial_hyper):
            if leaf.shape[:1] != (k,):
                raise ValueError(
                    f"every stacked leaf needs leading trial axis {k}, got "
                    f"shape {leaf.shape}")
        if active is None:
            active = jnp.ones((k,), bool)
        if offsets is None:
            offsets = jnp.zeros((k,), jnp.int32)
        else:
            offsets = jnp.asarray(offsets, jnp.int32)
            if offsets.shape != (k,):
                raise ValueError(
                    f"round offsets must be shape ({k},), got {offsets.shape}")
        return {"trial": trial_states, "hyper": trial_hyper,
                "active": jnp.asarray(active), "offset": offsets}

    def _cache_put(self, key: Any, value: Any) -> None:
        """Insert into the bounded epoch cache, evicting oldest-first."""
        while len(self._epoch_cache) >= self._epoch_cache_max:
            self._epoch_cache.pop(next(iter(self._epoch_cache)))
        self._epoch_cache[key] = value

    def _stacked_fns(self, trial_step: TrialStep,
                     trial_update: Optional[TrialUpdateFn]
                     ) -> Tuple[LocalStep, UpdateFn]:
        """vmap one trial's step/update over the trial axis.  Memoized per
        (trial_step, trial_update) so rung-segmented searches hit the
        jitted-epoch cache instead of retracing every segment."""
        key = ("stacked", trial_step, trial_update)
        if key in self._epoch_cache:
            return self._epoch_cache[key]

        def local_step(block: jnp.ndarray, carry: dict, r: jnp.ndarray) -> Any:
            # lane j sees its trial-local round r - offset[j]: a trial
            # admitted into a freed slot at a later global round trains on
            # the identical round sequence as a solo run from round 0
            return jax.vmap(lambda s, h, o: trial_step(block, s, r - o, h))(
                carry["trial"], carry["hyper"], carry["offset"])

        def upd(carry: dict, combined: Any, r: jnp.ndarray) -> dict:
            trial, hyper = carry["trial"], carry["hyper"]
            if trial_update is None:
                new = combined
            else:
                new = jax.vmap(lambda s, c, h, o: trial_update(s, c, r - o, h))(
                    trial, combined, hyper, carry["offset"])
            return {"trial": _mask_tree(carry["active"], new, trial),
                    "hyper": hyper, "active": carry["active"],
                    "offset": carry["offset"]}

        self._cache_put(key, (local_step, upd))
        return local_step, upd

    def run_stacked_rounds(self, table: Any, trial_states: Any,
                           trial_hyper: Any, trial_step: TrialStep,
                           num_rounds: int, *, combine: str = "mean",
                           update: Optional[TrialUpdateFn] = None,
                           active: Optional[jnp.ndarray] = None,
                           start_round: int = 0) -> Any:
        """Advance K device-stacked trials together over a resident table.

        ``trial_states`` is a pytree whose every leaf carries a leading
        (K, …) trial axis (see :func:`repro.tune.trials.tree_stack`);
        ``trial_hyper`` holds the per-trial scalar hyperparameters as (K,)
        leaves, so learning rates / regularizers are *traced* values and
        one compiled round advances all K candidates — K model-search
        trials for one jit and one collective per round instead of K.
        ``trial_step(block, state, r, hyper)`` and ``update(state,
        combined, r, hyper)`` describe ONE trial; this entry point vmaps
        them over the trial axis.  ``active`` masks trials stopped by the
        median rule: their states freeze but the round shape stays static.
        Returns the final stacked trial states.
        """
        carry = self._stacked_carry(trial_states, trial_hyper, active)
        step, upd = self._stacked_fns(trial_step, update)
        out = self.run_rounds(table, carry, step, num_rounds, combine=combine,
                              update=upd, start_round=start_round)
        return out["trial"]

    def run_stacked_epochs(self, stream: Iterator, trial_states: Any,
                           trial_hyper: Any, trial_step: TrialStep,
                           num_epochs: int, *, combine: str = "mean",
                           update: Optional[TrialUpdateFn] = None,
                           active: Optional[jnp.ndarray] = None,
                           chunks_per_epoch: int = 1,
                           checkpoint: Optional[CheckpointPolicy] = None,
                           rng: Optional[jnp.ndarray] = None,
                           start_epoch: int = 0,
                           round_offsets: Optional[jnp.ndarray] = None,
                           callbacks: Sequence[Callable] = (),
                           eval_fn: Optional[Callable] = None) -> Any:
        """Streaming twin of :meth:`run_stacked_rounds`: every epoch pulls
        ONE window from ``stream`` (shared by all K trials — the window
        crosses the host→device boundary once, not K times) and advances
        the stacked trial states through the PR-2 epoch scan, so searches
        inherit streaming's checkpoint/resume story unchanged.  Segmented
        callers (early-stopping rungs) pass ``start_epoch``/``active`` per
        segment; the compiled epoch function is cached across segments.

        ``round_offsets`` (K,) gives each lane a private round origin:
        lane ``j`` computes with trial-local round ``r - round_offsets[j]``
        — the mechanism slot-backfilling searches (ASHA) use to admit a
        fresh trial into a freed lane mid-run with its lr decay starting
        from zero.  Offsets must be multiples of ``chunks_per_epoch`` so
        the minibatch-chunk phase (``r % chunks``) is preserved.

        ``callbacks``/``eval_fn`` are the host-side hooks of
        :meth:`run_epochs`, presented at the trial level: each callback's
        env carries ``state`` = the stacked (K, …) trial tree, ``hyper``,
        and a host copy of ``active``; ``{"state"|"hyper"|"active": ...}``
        returns swap the matching carry component.  ``eval_fn(trial_states,
        epoch)`` returns the ``EvalEntry`` list for the boundary.
        Returns the final stacked trial states.
        """
        carry = self._stacked_carry(trial_states, trial_hyper, active,
                                    round_offsets)
        step, upd = self._stacked_fns(trial_step, update)
        run_callbacks: Sequence[Callable] = ()
        run_eval = None
        if callbacks:
            run_callbacks = [_stacked_callback_shim(cb) for cb in callbacks]
        if eval_fn is not None:
            run_eval = lambda carry, epoch: eval_fn(carry["trial"], epoch)  # noqa: E731
        out = self.run_epochs(stream, carry, step, num_epochs, combine=combine,
                              update=upd, chunks_per_epoch=chunks_per_epoch,
                              checkpoint=checkpoint, rng=rng,
                              start_epoch=start_epoch,
                              callbacks=run_callbacks, eval_fn=run_eval)
        return out["trial"]

    def __repr__(self) -> str:  # pragma: no cover
        where = (f"mesh{tuple(self.mesh.shape.items())}" if self.mesh is not None
                 else f"emulated[{self.num_shards}]")
        return (f"DistributedRunner({where}, schedule={self.schedule.value}, "
                f"donate={self.donate})")
