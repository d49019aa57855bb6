"""Continuous-batching decode engine: one shared KV cache, per-slot
positions, mid-decode backfill.

The serving-side analogue of the paper §III-C 'Model makes predictions'
contract, scaled from one ``predict`` call to a request stream.  Device
work is three jitted functions:

  * **ragged prefill** — newly admitted prompts of *mixed* lengths are
    right-padded and prefilled together (``TransformerLM.prefill_ragged``);
    pad columns never enter the shared cache, and SSD blocks hold their
    state through a pad tail, so each slot's cache (K/V of attention
    blocks, ``h`` and conv taps of SSD blocks, side by side) is what a lone
    batch-1 prefill would have written.  Architectures whose state a pad
    tail would corrupt (RG-LRU blocks, MoE capacity routing, encoder/vision
    frontends) prefill per-request into a batch-1 cache that is scattered
    into the slot instead.  A wave's sub-cache starts from zeros and the
    scatter overwrites each admitted slot's whole row, so a reused slot
    carries nothing of its last request.
  * **fused decode** — ONE masked decode step advances every busy slot
    regardless of where each sits in its sequence: ``pos`` is a (B,)
    vector and the attention mask is per-slot (``models/layers/attention``).
  * **cache scatter** — drops a prefilled request into its slot of the
    shared cache.

The scatter donates the shared cache, and the ragged prefill makes its
zeroed sub-cache inside the program, so admission never holds a second
copy of the shared cache or a zeroed sub-cache beside the prefilled one.

Host spans (``repro.core.trace``): ``serve.admit`` around a turn's
admission (wave inputs, prefill, scatter, first tokens), inside it
``serve.prefill`` around each wave's prefill and scatter dispatch and the
read-back of its first tokens, and ``serve.decode`` around the decode
step's dispatch and the read-back of its tokens.  Counters:
``serve.admit_waves`` (prefill programs run), ``serve.prefill_tokens``
(positions they computed, pad included) and ``serve.prefill_pad_tokens``
(of those, pad columns and dummy rows).

Slot admission, retirement, and backfill are host-side and owned by
:class:`repro.serve.scheduler.SlotScheduler`; the engine is the device
half.  ``run_static`` keeps the pre-refactor behavior (equal-length
grouping, no backfill) as the reference baseline — greedy token streams
from both paths are identical per request (asserted in
``tests/test_serve_continuous.py``; measured in
``benchmarks/serving_throughput.py``).

The mesh/rules the engine runs under come from
:func:`repro.launch.mesh.serving_setup` (or its host-sized twin); passing
``mesh=`` shards the cache's slot axis over the mesh data axes via the
same logical-rule machinery as params (``serve/step.py`` +
``sharding/rules``).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.models.config import ATTENTION_KINDS, ArchConfig
from repro.models.transformer import RAGGED_KINDS, TransformerLM
from repro.serve.scheduler import Request, SlotScheduler

__all__ = ["Request", "ServeEngine"]


def _now_zero() -> float:
    return 0.0


def _count_wave(positions: int, real: int) -> None:
    """Count one prefill program: ``positions`` computed, ``real`` of them
    prompt tokens of admitted requests."""
    trace.count("serve.admit_waves")
    trace.count("serve.prefill_tokens", positions)
    trace.count("serve.prefill_pad_tokens", positions - real)


def serve_scatter(cache, sub_cache, slots: jnp.ndarray):
    """Drop ``sub_cache`` (batch = len(slots)) into ``cache`` at slot
    indices ``slots`` along the batch axis (axis 1 — axis 0 is the
    stacked-periods axis).  Out-of-range slot indices are dropped: the
    ragged prefill pads its admission wave to a fixed batch with dummy
    rows routed to slot ``num_slots``."""
    return jax.tree.map(
        lambda full, sub: full.at[:, slots].set(sub, mode="drop"),
        cache, sub_cache)


class ServeEngine:
    """Fixed-slot continuous-batching engine over a request stream.

    ``batch_size`` decode slots share one KV cache with a real batch
    dimension; requests are admitted into free slots (backfilled
    mid-decode as others retire) and every busy slot advances through one
    fused per-slot-position decode step per token.  Greedy outputs are
    identical to the slot-at-a-time path (tested).
    """

    def __init__(self, cfg: ArchConfig, params, batch_size: int, max_seq: int,
                 greedy: bool = True, mesh=None, rules=None, param_axes=None,
                 prefix_cache=None, pad_to: int = 8):
        self.cfg = cfg
        self.model = TransformerLM(cfg)
        self.batch = int(batch_size)
        self.max_seq = int(max_seq)
        # admission waves pad their sequence to a multiple of this: one
        # compiled prefill per bucket (and wave size) that traffic reaches
        self.pad_to = int(pad_to)
        self.greedy = greedy
        self.mesh = mesh
        if mesh is not None:
            from repro.sharding.rules import DEFAULT_RULES, shardings_for
            self.rules = rules if rules is not None else DEFAULT_RULES
            if param_axes is not None:
                params = jax.device_put(
                    params, shardings_for(param_axes, params, mesh, self.rules))
        if cfg.quantize != "none":
            if mesh is not None:
                raise ValueError(
                    "quantize= is a single-host serving knob: the mesh "
                    "param-sharding tables predate QTensor leaves")
            from repro.models.layers.quant import quantize_model_params
            params = quantize_model_params(params, cfg.quantize)
        self.params = params
        # ragged (batched mixed-length) prefill is exact only where no
        # cross-slot state exists and every block's state survives a pad
        # tail; everything else prefills per-request and scatters into its
        # slot
        self.ragged_ok = (
            all(k in RAGGED_KINDS for k in cfg.pattern)
            and not cfg.num_experts and not cfg.cross_attention
            and not cfg.vision_tokens)
        # each program is named for the trace: jit_serve_prefill and so on
        def serve_prefill(p, t, c):
            return self.model.prefill(p, t, c)

        def serve_prefill_ragged(p, t, n):
            # the wave's sub-cache starts from zeros made inside the program
            # (no second cache-sized argument); a reused slot carries nothing
            return self.model.prefill_ragged(
                p, t, n, self.model.init_cache(t.shape[0], self.max_seq))

        def serve_decode(p, t, pos, c):
            return self.model.decode_step(p, t, pos, c)

        self._prefill = jax.jit(serve_prefill)
        self._prefill_ragged = jax.jit(serve_prefill_ragged)
        self._decode = jax.jit(serve_decode)
        self._scatter = jax.jit(serve_scatter, donate_argnums=0)
        # radix prefix KV cache (serve/prefix_cache.py): admission becomes
        # match → restore cached blocks → prefill the uncached tail only
        self.prefix_cache = prefix_cache
        if prefix_cache is not None:
            if not (self.ragged_ok
                    and all(k in ATTENTION_KINDS for k in cfg.pattern)):
                raise ValueError(
                    "prefix_cache requires the ragged-prefill path of an "
                    "attention-only dense decoder (no recurrent state "
                    "snapshots)")
            if cfg.cache_dtype == "int8":
                raise ValueError(
                    "prefix_cache needs exact KV restore, but cache_dtype="
                    "'int8' stores quantized K/V while prefill attends raw "
                    "— greedy streams would not be bit-identical cache-on "
                    "vs off.  Use quantize= for int8 weights instead")
            prefix_cache.bind(self.model, self.max_seq)

            def serve_prefill_ragged_start(p, t, n, s, c):
                return self.model.prefill_ragged(p, t, n, c, start_pos=s)

            self._prefill_ragged_start = jax.jit(serve_prefill_ragged_start)

    # ------------------------------------------------------------------ #
    # shared-cache plumbing
    # ------------------------------------------------------------------ #
    def init_shared_cache(self):
        """The engine's one KV cache: batch dim = decode slots.  With a
        mesh, the slot axis is sharded over the mesh data axes ("slot
        sharding") through the same logical-rule table as params."""
        cache = self.model.init_cache(self.batch, self.max_seq)
        if self.mesh is not None:
            from repro.serve.step import cache_axes
            from repro.sharding.rules import shardings_for
            cache = jax.device_put(
                cache, shardings_for(cache_axes(self.cfg), cache, self.mesh,
                                     self.rules))
        return cache

    # ------------------------------------------------------------------ #
    # admission → prefill
    # ------------------------------------------------------------------ #
    def _prefill_into(self, cache, admits: List[Tuple[int, Request]],
                      pad_to: Optional[int] = None,
                      wave_pad: Optional[int] = None) -> Tuple[Any, np.ndarray]:
        """Prefill the admitted requests and scatter them into their slots.
        Returns (cache, first greedy token per admit).

        Mixed lengths go through ONE ragged right-padded prefill when the
        architecture allows it (``prefill_ragged``).  The admission wave is
        padded along *both* axes to keep compiled shapes stable across
        waves: sequence to a ``pad_to`` bucket, batch to a *wave bucket* —
        a single-request backfill (the dominant steady-state wave once
        slots start retiring one at a time) runs at batch 1, anything
        larger pads to the engine's slot count with dummy length-1 rows
        whose scatter destination is out-of-range (dropped).  Two compiled
        prefills per sequence bucket, whatever the wave size.
        ``wave_pad`` overrides the batch pad target (the router passes
        power-of-2 wave buckets so a fleet-sized cache never pays a
        full-fleet prefill for a two-request backfill).  Architectures the
        ragged path excludes prefill per-request (one compile per distinct
        prompt length) and scatter batch-1 caches.
        """
        pad_to = pad_to or self.pad_to
        slots = np.asarray([s for s, _ in admits], np.int32)
        reqs = [r for _, r in admits]
        lens = np.asarray([len(r.prompt) for r in reqs], np.int32)
        if np.any(lens + np.asarray([r.max_new_tokens for r in reqs]) >
                  self.max_seq):
            raise ValueError("prompt + max_new_tokens exceeds max_seq")
        if self.ragged_ok:
            n, B = len(reqs), self.batch
            if wave_pad is not None:
                wb = max(min(int(wave_pad), B), n)
            else:
                wb = 1 if n == 1 else B           # wave bucket (batch pad)
            if self.prefix_cache is not None:
                return self._prefill_into_cached(cache, reqs, slots, lens,
                                                 wb, pad_to)
            S = min(int(-(-int(lens.max()) // pad_to) * pad_to), self.max_seq)
            padded = np.zeros((wb, S), np.int32)
            full_lens = np.ones(wb, np.int32)     # dummy rows: 1 real token
            full_slots = np.full(wb, B, np.int32)  # dummy rows: OOB → dropped
            for i, r in enumerate(reqs):
                padded[i, : lens[i]] = r.prompt
                full_lens[i] = lens[i]
                full_slots[i] = slots[i]
            _count_wave(wb * S, int(lens.sum()))
            with trace.span("serve.prefill"):
                logits, sub = self._prefill_ragged(
                    self.params, jnp.asarray(padded), jnp.asarray(full_lens))
                cache = self._scatter(cache, sub, jnp.asarray(full_slots))
                first = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                                   np.int32)
            return cache, first[:n]
        first = np.zeros(len(reqs), np.int32)
        for i, r in enumerate(reqs):
            sub = self.model.init_cache(1, self.max_seq)
            _count_wave(len(r.prompt), len(r.prompt))
            with trace.span("serve.prefill"):
                logits, sub = self._prefill(
                    self.params, jnp.asarray(r.prompt, jnp.int32)[None, :],
                    sub)
                cache = self._scatter(cache, sub,
                                      jnp.asarray(slots[i : i + 1]))
                first[i] = int(jnp.argmax(logits[0, -1]))
        return cache, first

    def _prefill_into_cached(self, cache, reqs: List[Request],
                             slots: np.ndarray, lens: np.ndarray,
                             wb: int, pad_to: int):
        """Admission wave with the radix prefix cache: match each prompt's
        longest cached block-aligned prefix (pinned), then **split the
        wave** — miss rows (no cached prefix) run the plain ragged prefill
        (the exact cache-off compiled program, so their streams are
        trivially identical), hit rows get their matched blocks scattered
        into a sub-cache with one jitted restore and run ONE ragged
        **tail** prefill over the uncached suffixes
        (``prefill_ragged(start_pos=)``) whose sequence bucket is sized by
        the longest *tail* alone.  Without the split, a single miss in an
        80%-shared wave dragged every hit row's bucket back to full prompt
        width — through the wider prefix-attending program, i.e. slower
        than no cache at all.  Finally every full prompt block (fresh or
        ``valid_end``-improved) is gathered back into the pool with one
        jitted extract per sub-cache."""
        pc = self.prefix_cache
        n, B = len(reqs), self.batch
        matches = [pc.match(r.prompt) for r in reqs]
        starts = np.asarray([m.length for m in matches], np.int32)
        hit = np.nonzero(starts > 0)[0]
        miss = np.nonzero(starts == 0)[0]
        first = np.zeros(n, np.int32)
        groups = []                               # (sub_cache, req indices)

        def _bucket_batch(k: int) -> int:
            p = 1
            while p < k:
                p <<= 1
            return min(p, wb)                     # 1,2,4,… capped at wave pad

        if len(miss):
            idx = miss
            k, wbg = len(idx), _bucket_batch(len(miss))
            S = min(int(-(-int(lens[idx].max()) // pad_to) * pad_to),
                    self.max_seq)
            padded = np.zeros((wbg, S), np.int32)
            glens = np.ones(wbg, np.int32)        # dummy rows: 1 real token
            gslots = np.full(wbg, B, np.int32)    # dummy rows: OOB → dropped
            for j, i in enumerate(idx):
                padded[j, : lens[i]] = reqs[i].prompt
                glens[j] = lens[i]
                gslots[j] = slots[i]
            _count_wave(wbg * S, int(lens[idx].sum()))
            logits, sub = self._prefill_ragged(
                self.params, jnp.asarray(padded), jnp.asarray(glens))
            cache = self._scatter(cache, sub, jnp.asarray(gslots))
            first[idx] = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                                    np.int32)[:k]
            groups.append((sub, idx))
        if len(hit):
            idx = hit
            k, wbg = len(idx), _bucket_batch(len(hit))
            tails = lens[idx] - starts[idx]       # ≥ 1 (match leaves a tail)
            S = min(int(-(-int(tails.max()) // pad_to) * pad_to),
                    self.max_seq)
            padded = np.zeros((wbg, S), np.int32)
            glens = np.ones(wbg, np.int32)
            gstarts = np.zeros(wbg, np.int32)
            gslots = np.full(wbg, B, np.int32)
            for j, i in enumerate(idx):
                padded[j, : tails[j]] = reqs[i].prompt[starts[i]:]
                glens[j] = tails[j]
                gstarts[j] = starts[i]
                gslots[j] = slots[i]
            sub = self.model.init_cache(wbg, self.max_seq)
            restores = [(j, node.block_id, d * pc.block_size,
                         int(starts[i]))
                        for j, i in enumerate(idx)
                        for d, node in enumerate(matches[i].nodes)]
            sub = pc.restore_into(sub, restores)
            _count_wave(wbg * S, int(tails.sum()))
            logits, sub = self._prefill_ragged_start(
                self.params, jnp.asarray(padded), jnp.asarray(glens),
                jnp.asarray(gstarts), sub)
            cache = self._scatter(cache, sub, jnp.asarray(gslots))
            first[idx] = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                                    np.int32)[:k]
            groups.append((sub, idx))
        # record this wave's full prompts: freshly computed blocks (and
        # blocks whose valid_end improves) flow back into the pool from
        # whichever sub-cache holds the row
        for sub, idx in groups:
            inserts = [(j, bid, st)
                       for j, i in enumerate(idx)
                       for bid, st in pc.plan_insert(reqs[i].prompt)]
            pc.extract_from(sub, inserts)
        for r, m in zip(reqs, matches):
            r.cached_prefill = int(m.length)
            pc.release(m)
        return cache, first

    # ------------------------------------------------------------------ #
    # continuous-batching loop
    # ------------------------------------------------------------------ #
    def run(self, requests: List[Request],
            scheduler: Optional[SlotScheduler] = None,
            now_fn=None) -> List[Request]:
        """Serve ``requests`` with continuous batching: admit into free
        slots, advance all busy slots through one fused decode step per
        token, retire on EOS/``max_new_tokens``, and backfill freed slots
        from the queue mid-decode.  ``now_fn`` supplies the clock for the
        scheduler's latency accounting (default: a frozen 0 clock, which
        keeps unit tests deterministic); requests whose ``arrival`` lies in
        the future are held back until the clock reaches them."""
        sched = scheduler or SlotScheduler(self.batch)
        now = now_fn or _now_zero
        if now is _now_zero and any(r.arrival > 0 for r in requests):
            raise ValueError("requests with a future arrival need an "
                             "advancing clock: pass now_fn="
                             "time.perf_counter (or rebase arrivals to 0)")
        for r in requests:
            sched.submit(r)

        B = self.batch
        cache = self.init_shared_cache()
        toks = np.zeros(B, np.int32)     # pending (unemitted) token per slot
        pos = np.zeros(B, np.int32)      # decode position per slot
        while sched.has_work():
            t = now()
            admits = sched.admit(t)
            if not admits and sched.busy == 0:
                # nothing running and nothing admissible yet: the next
                # arrival is in the future — let the clock catch up
                nxt = sched.next_arrival()
                if nxt is None:
                    break  # defensive; has_work() should have said no
                time.sleep(min(max(nxt - now(), 0.0), 0.001))
                continue
            if admits:
                with trace.span("serve.admit"):
                    cache, first = self._prefill_into(cache, admits)
                    for (slot, req), tok in zip(admits, first):
                        toks[slot] = tok
                        pos[slot] = len(req.prompt)
            # emit one token per busy slot; retire EOS / exhausted slots
            t = now()
            for slot in range(B):
                req = sched.slots[slot]
                if req is None:
                    continue
                if req.max_new_tokens == 0:
                    sched.retire(slot, t)
                    continue
                req.out_tokens.append(int(toks[slot]))
                if len(req.out_tokens) >= req.max_new_tokens or (
                        req.eos_id is not None and toks[slot] == req.eos_id):
                    sched.retire(slot, t)
            if sched.busy == 0:
                continue  # all retired; backfill (or finish) next iteration
            # ONE fused masked step advances every slot, each at its own pos
            with trace.span("serve.decode"):
                logits, cache = self._decode(
                    self.params, jnp.asarray(toks[:, None], jnp.int32),
                    jnp.asarray(pos, jnp.int32), cache)
                step_toks = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                                       np.int32)
            for slot in range(B):
                if sched.slots[slot] is not None:
                    toks[slot] = step_toks[slot]
                    pos[slot] += 1
        return requests

    # ------------------------------------------------------------------ #
    # reference paths (parity + benchmark baseline)
    # ------------------------------------------------------------------ #
    def _run_one(self, req: Request) -> Request:
        """Slot-at-a-time reference: prefill one request, then greedy-decode
        token by token with a batch-1 cache.  The parity oracle for the
        continuous path."""
        S = len(req.prompt)
        cache = self.model.init_cache(1, self.max_seq)
        logits, cache = self._prefill(
            self.params, jnp.asarray(req.prompt, jnp.int32)[None, :], cache)
        pos = S
        tok = int(jnp.argmax(logits[0, -1]))
        for _ in range(req.max_new_tokens):
            req.out_tokens.append(tok)
            if req.eos_id is not None and tok == req.eos_id:
                break
            logits, cache = self._decode(self.params,
                                         jnp.asarray([[tok]], jnp.int32),
                                         jnp.asarray(pos, jnp.int32), cache)
            tok = int(jnp.argmax(logits[0, -1]))
            pos += 1
        req.done = True
        return req

    def run_static(self, requests: List[Request]) -> List[Request]:
        """The pre-refactor static engine, kept as the benchmark baseline:
        requests with equal prompt length group into one shared-position
        batch; every other request decodes slot-at-a-time.  No admission
        queue, no backfill — on a mixed-length workload this degenerates
        toward slot-at-a-time, which is exactly what
        ``benchmarks/serving_throughput.py`` measures against."""
        groups: Dict[int, List[int]] = {}
        for i, r in enumerate(requests):
            groups.setdefault(len(r.prompt), []).append(i)
        for plen, idxs in groups.items():
            if len(idxs) == 1:
                self._run_one(requests[idxs[0]])
            else:
                self._run_group([requests[i] for i in idxs], plen)
        return requests

    def _run_group(self, reqs: List[Request], plen: int) -> None:
        """Batched decode for equal-length prompts: shared positions, one
        cache with a true batch dimension, per-slot retirement masks."""
        B = len(reqs)
        prompts = jnp.asarray(np.stack([r.prompt for r in reqs]), jnp.int32)
        cache = self.model.init_cache(B, self.max_seq)
        logits, cache = self._prefill(self.params, prompts, cache)
        toks = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
        pos = plen
        max_new = max(r.max_new_tokens for r in reqs)
        active = np.ones(B, bool)
        for step in range(max_new):
            for b, r in enumerate(reqs):
                if not active[b]:
                    continue
                r.out_tokens.append(int(toks[b]))
                if len(r.out_tokens) >= r.max_new_tokens or (
                        r.eos_id is not None and toks[b] == r.eos_id):
                    active[b] = False
                    r.done = True
            if not active.any() or step == max_new - 1:
                break
            logits, cache = self._decode(self.params,
                                         jnp.asarray(toks[:, None], jnp.int32),
                                         jnp.asarray(pos, jnp.int32), cache)
            toks = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
            pos += 1
        for r in reqs:
            r.done = True

    # ------------------------------------------------------------------ #
    # warmup (perf reporting excludes compile time)
    # ------------------------------------------------------------------ #
    def warmup(self, prompt_lens: Sequence[int] = (),
               pad_to: Optional[int] = None) -> None:
        """Compile the fused decode step, the cache scatter, and every
        prefill shape the given prompt lengths will hit, so serving (and
        the launcher's perf report) never pays compile time mid-stream.

        On the ragged path the compiled prefill shape depends only on the
        sequence *bucket* and the wave bucket (batch 1 for solo backfills,
        the slot count otherwise), so two warm prefills per distinct
        sequence bucket cover admission waves of any size; the per-request
        fallback path compiles one prefill per distinct prompt length
        instead.

        With a prefix cache the same waves run the match→restore→tail
        pipeline: the first wave per bucket misses (compiling the
        full-length tail prefill and the pool extract), the second wave
        hits the blocks the first just inserted (compiling the restore
        scatter and the short-tail bucket).  Tail buckets are
        traffic-dependent, so an unseen tail length can still cost one
        mid-stream compile of the (small) tail program; the warm-probe
        blocks are dropped from the trie afterwards (``reset``) so warmup
        never pollutes live hit-rate stats.
        """
        pad_to = pad_to or self.pad_to
        lens = sorted(set(int(n) for n in prompt_lens))
        cache = self.init_shared_cache()
        if lens and self.ragged_ok:
            buckets = sorted(set(
                min(-(-n // pad_to) * pad_to, self.max_seq) for n in lens))
            for b in buckets:
                req = Request(prompt=np.zeros(min(b, self.max_seq - 1),
                                              np.int32), max_new_tokens=1)
                cache, _ = self._prefill_into(cache, [(0, req)], pad_to=pad_to)
                if self.batch > 1:
                    wave = [(s, Request(prompt=np.zeros(
                        min(b, self.max_seq - 1), np.int32), max_new_tokens=1))
                        for s in range(min(2, self.batch))]
                    cache, _ = self._prefill_into(cache, wave, pad_to=pad_to)
        elif lens:
            for n in lens:
                req = Request(prompt=np.zeros(n, np.int32), max_new_tokens=1)
                cache, _ = self._prefill_into(cache, [(0, req)], pad_to=pad_to)
        _ = self._decode(self.params,
                         jnp.asarray(np.zeros((self.batch, 1), np.int32)),
                         jnp.asarray(np.zeros(self.batch, np.int32)), cache)
        jax.block_until_ready(_)
        if self.prefix_cache is not None:
            self.prefix_cache.reset()
