"""Replica engine: the serial backend behind the admission layer.

Two execution modes share one interface:

* ``RealEngine`` — jitted prefill + fused on-device greedy decode of an
  actual LM in plain ``jax.numpy`` (models/attention.py; no Pallas kernel
  is on this path).  The sidecar's ``--backend real`` serves full configs
  with it; the examples, the serve benchmark and the integration tests
  run reduced configs;
* ``SimEngine`` — virtual-clock engine using a ServiceTimeModel (used by the
  queueing benchmarks, where thousands of requests are served);
* ``BatchedRealEngine`` — bounded-concurrency micro-batching over
  ``RealEngine``'s model: ``n_lanes`` concurrent requests under a
  KV-memory budget (serving/batching.py), lane-batched segment decode
  (serving/generate.py ``LaneDecoder``), retire-and-back-fill at segment
  boundaries.  Per-request greedy tokens stay bitwise-equal to serial
  runs.

The first two are strictly serial: one request in flight per replica — the
regime the paper targets (§2.3).  Disconnect semantics per §3.4:
cancellation while queued removes the heap entry (lazy); cancellation
mid-generation stops the fused loop at the next segment boundary
(``request_cancel``; per-lane eviction on the batched engine), draining the
response to free the dispatch slot within ``segment_len`` tokens.

``RealEngine`` generation path (PR 3):

* **Bucketed prefill** — prompts are right-padded to a small geometric set
  of lengths (powers of two up to ``max_len``; see
  ``generate.geometric_buckets``), so a mixed-length admission stream
  triggers O(log max_len) jit compiles instead of one per distinct prompt
  length.  The true ``prompt_len`` rides into the jitted prefill as a
  dynamic scalar: logits are gathered at ``prompt_len - 1`` and the cache
  fill level is reset to ``prompt_len`` (models/model.py).  Bucketing
  engages when every block keeps pads out of its state: attention (causal,
  pads at the end) and Mamba (pads masked out of the recurrence,
  models/ssm.py); other stacks prefill at exact lengths (the seed
  behavior).
* **Ring-buffer KV cache** — caches hold ``max_len`` slots; decode writes
  step ``t`` at slot ``t % max_len`` (models/attention.py), so capacity is
  an attention-window bound, never a per-request reallocation.
* **Fused decode** — ``generate`` drives ``serving.generate.FusedDecoder``:
  segments of ``segment_len`` tokens run in one jitted ``lax.while_loop``
  with the EOS/length stop on device and the caches donated in place; the
  host syncs once per segment.  The seed per-token Python loop is retained
  as ``generate_reference`` — the bitwise token-sequence equivalence oracle
  (tests/test_generate.py), matching the PR 1/PR 2 oracle pattern.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import numpy as np

from repro.serving.observability import NO_REGION
from repro.serving.service_time import ServiceTimeModel


class SimEngine:
    """Virtual-time serial backend."""

    def __init__(self, model: ServiceTimeModel, replica_id: int = 0):
        self.model = model
        self.replica_id = replica_id
        self.busy_until = 0.0
        self.served = 0

    def engine_stats(self) -> dict:
        """Wire-facing stats snapshot (sidecar /healthz, /metrics)."""
        return {"replica": self.replica_id, "served": self.served}

    def execute(self, start: float, prompt_tokens: int,
                output_tokens: int) -> tuple[float, float]:
        """Returns (ttft_s, service_s); advances the virtual clock."""
        service = self.model.service(prompt_tokens, output_tokens)
        ttft = self.model.overhead_s + prompt_tokens / self.model.prefill_tok_per_s
        self.busy_until = start + service
        self.served += 1
        return ttft, service


def _top2(row: np.ndarray) -> tuple:
    """(largest, second-largest) entry of a logit row."""
    second, first = np.partition(row, -2)[-2:]
    return float(first), float(second)


# Padded (bucketed) prefill is only used when every block keeps pad tokens
# out of its state: attention is causal with the pads at the end, and a
# Mamba mixer masks them out of its recurrence.  xLSTM recurrences fold
# pads into their state and MoE capacity routing lets pads evict real
# tokens.
_BUCKET_SAFE_KINDS = ("attn", "mamba")


def _pure_attention(cfg) -> bool:
    """Speculative verification and the paged pool work on attention
    caches alone."""
    return all(k == "attn" for k in cfg.block_pattern)


def recurrent_state_bytes(cfg) -> int:
    """Bytes of recurrent state (Mamba conv and SSM, xLSTM cells) one
    request carries through decode, whatever its length."""
    import jax
    from repro.configs.base import SUBQUADRATIC_KINDS
    from repro.models.model import LM
    caches = jax.eval_shape(lambda: LM(cfg).init_cache(1, 1))
    return sum(leaf.size * leaf.dtype.itemsize
               for kind, c in zip(cfg.block_pattern, caches)
               if kind in SUBQUADRATIC_KINDS for leaf in jax.tree.leaves(c))


class RealEngine:
    """Actual LM decode on device: bucketed prefill, then fused
    segments of greedy decode."""

    def __init__(self, cfg, params=None, replica_id: int = 0, seed: int = 0,
                 max_len: int = 256, segment_len: int = 16,
                 draft_cfg=None, draft_params=None, draft_k: int = 0,
                 draft_seed: int = 0):
        import jax
        import jax.numpy as jnp
        from repro.models.model import LM
        from repro.serving.generate import (FusedDecoder,
                                            SpeculativeDecoder,
                                            geometric_buckets)

        self.cfg = cfg
        self.lm = LM(cfg)
        self.replica_id = replica_id
        self.max_len = max_len
        self.segment_len = segment_len
        self.params = params if params is not None \
            else self.lm.init(jax.random.key(seed))
        self.busy_until = 0.0
        self.served = 0
        self._cancel = False
        # optional serving.faults.FaultInjector: polled at fused-decode
        # segment boundaries (same join points as cancellation), where an
        # injected crash surfaces as an EngineCrash raise out of generate
        self.fault_injector = None
        # optional serving.observability.FlightRecorder: ``generate``
        # times its prefill/decode regions on it (on the recorder's
        # clock); the batched lane loop stamps per-lane
        # prefill/decode/decode_segment spans (from the caller's now_fn)
        self.recorder = None
        self._pending_items: list = []
        # pad tokens run through bucketed prefills (masked out of every
        # state), and the recurrent state a request carries
        self.prefill_pad_tokens = 0
        self.recurrent_state_bytes = recurrent_state_bytes(cfg)

        self._bucketing = all(k in _BUCKET_SAFE_KINDS
                              for k in cfg.block_pattern)
        self.buckets = geometric_buckets(max_len) if self._bucketing else ()
        # One jit; retraces once per bucket shape (prompt_len is dynamic).
        self._prefill = jax.jit(
            lambda p, toks, plen: self.lm.prefill(
                p, {"tokens": toks}, pad_to=max_len, prompt_len=plen))
        self._decode = jax.jit(self.lm.decode_step)       # oracle path
        self._decoders = {segment_len: FusedDecoder(self.lm, max_len,
                                                    segment_len)}
        # speculative decoding (draft_k >= 1 + a draft config): the small
        # draft model proposes token chains the target verifies in one
        # multi-position forward.  K=0 keeps the plain fused path even
        # when a draft config is supplied.
        self.draft_cfg = draft_cfg
        self.draft_k = int(draft_k)
        self.speculative = draft_cfg is not None and self.draft_k > 0
        self.draft_lm = None
        self.draft_params = None
        if self.speculative:
            if not _pure_attention(cfg):
                raise ValueError(
                    "speculative decoding needs a pure-attention stack "
                    f"(got pattern {cfg.block_pattern}): the verify "
                    "forward is an attention-cache operation")
            if not _pure_attention(draft_cfg):
                raise ValueError(
                    "draft model needs a pure-attention stack "
                    f"(got pattern {draft_cfg.block_pattern})")
            self.draft_lm = LM(draft_cfg)
            self.draft_params = draft_params if draft_params is not None \
                else self.draft_lm.init(jax.random.key(draft_seed))
            self._draft_prefill = jax.jit(
                lambda p, toks, plen: self.draft_lm.prefill(
                    p, {"tokens": toks}, pad_to=max_len, prompt_len=plen))
            self._spec_decoder = SpeculativeDecoder(
                self.lm, self.draft_lm, max_len, self.draft_k)

    # ---------------------------------------------------------------- admin
    def request_cancel(self) -> None:
        """§3.4 mid-generation disconnect: the fused loop observes this flag
        at the next segment boundary and drains."""
        self._cancel = True

    def engine_stats(self) -> dict:
        """Wire-facing stats snapshot (sidecar /healthz, /metrics)."""
        return {"replica": self.replica_id, "served": self.served,
                "speculative": self.speculative,
                "prefill_pad_tokens": self.prefill_pad_tokens,
                "recurrent_state_bytes": self.recurrent_state_bytes}

    def _decoder(self, segment_len: int):
        dec = self._decoders.get(segment_len)
        if dec is None:
            from repro.serving.generate import FusedDecoder
            dec = FusedDecoder(self.lm, self.max_len, segment_len)
            self._decoders[segment_len] = dec
        return dec

    # -------------------------------------------------------------- prefill
    def bucket(self, plen: int) -> int:
        """The length a ``plen``-token prompt prefills at: its bucket, or
        exactly ``plen`` where the stack does not bucket."""
        from repro.serving.generate import bucket_for
        return bucket_for(plen, self.buckets) if self._bucketing else plen

    def _run_prefill(self, prompt_ids: np.ndarray, prefill=None,
                     params=None):
        """Bucket-pad + prefill.  Returns (last_logits, caches, prompt_len).
        ``prefill``/``params`` override the target model's (the draft
        model prefills through the same bucketing so its cache rows are
        laid out identically)."""
        import jax.numpy as jnp
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        plen = len(ids)
        if plen < 1:
            raise ValueError("empty prompt: prefill needs >= 1 token "
                             "(dynamic_slice would silently clamp to 0)")
        bucket = self.bucket(plen)
        self.prefill_pad_tokens += bucket - plen
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = ids
        logits, caches = (prefill or self._prefill)(
            self.params if params is None else params,
            jnp.asarray(toks), jnp.asarray(plen, jnp.int32))
        return logits, caches, plen

    def _run_prefill_group(self, ids_list, pad_rows: Optional[int] = None,
                           prefill=None, params=None):
        """One padded prefill for prompts sharing a bucket (lane
        admission batches).  Returns (last_logits (k, V), caches with
        per-row fill levels, plens).  Rows are padded exactly as their
        solo bucketed prefill would be, so per-row results match the
        serial path; callers group by bucket before calling.

        The batch axis is padded to ``pad_rows`` (dummy single-token
        rows, sliced off before returning): back-fill group sizes vary
        per drain, and compiling one prefill program per exact (k,
        bucket) pair would pay a jit compile mid-drain for every new
        combination.  The batched engine pads every group to its lane
        count — ONE program per bucket, like the serial engine — trading
        <= lanes x of a ~ms prefill for never compiling (~0.7 s) on the
        serving path.  Default (``pad_rows=None``): the next power of
        two."""
        import jax
        import jax.numpy as jnp
        ids_list = [np.asarray(i, np.int32).reshape(-1) for i in ids_list]
        plens = [len(i) for i in ids_list]
        if min(plens) < 1:
            raise ValueError("empty prompt in prefill group")
        buckets = {self.bucket(p) for p in plens}
        if len(buckets) != 1:
            raise ValueError(f"prefill group spans buckets {buckets}")
        bucket = buckets.pop()
        self.prefill_pad_tokens += sum(bucket - p for p in plens)
        k = len(ids_list)
        if pad_rows is not None:
            if k > pad_rows:
                raise ValueError(f"group of {k} exceeds pad_rows {pad_rows}")
            kp = pad_rows
        else:
            kp = 1
            while kp < k:
                kp *= 2
        toks = np.zeros((kp, bucket), np.int32)
        for r, ids in enumerate(ids_list):
            toks[r, :len(ids)] = ids
        logits, caches = (prefill or self._prefill)(
            self.params if params is None else params, jnp.asarray(toks),
            jnp.asarray(plens + [1] * (kp - k), jnp.int32))
        if kp != k:
            logits = logits[:k]
            caches = jax.tree.map(lambda x: x[:, :k], caches)
        return logits, caches, plens

    # ------------------------------------------------------------- generate
    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int = 32,
                 eos_id: Optional[int] = None, cancel_cb=None,
                 segment_len: Optional[int] = None, on_segment=None,
                 req_id: Optional[int] = None) -> dict:
        """Fused greedy decode.  prompt_ids: (S,) ints.

        Returns {"tokens", "ttft_s", "service_s", "cancelled", "segments"}.
        ``cancel_cb`` (optional nullary) is polled with the engine's own
        cancel flag between scan segments.  ``on_segment(new_tokens)``
        streams tokens out at each segment boundary (the sidecar's SSE
        flush points — see :meth:`FusedDecoder.decode`).

        With a recorder attached, the request ``req_id``'s ``prefill``
        (the dispatch through the host argmax of the first token),
        ``decode`` and the decode loop's regions are timed here, on the
        replica's track.
        """
        self._cancel = False
        rec = self.recorder
        region = None if rec is None else functools.partial(
            rec.region, req_id=req_id, track=f"replica{self.replica_id}")
        t0 = time.monotonic()
        with NO_REGION if region is None else region(
                "prefill", tokens=len(prompt_ids),
                bucket=self.bucket(len(prompt_ids))):
            logits, caches, plen = self._run_prefill(prompt_ids)
            tok = int(np.argmax(np.asarray(logits)[0]))
        ttft = time.monotonic() - t0

        def cancelled():
            if self.fault_injector is not None:
                # may raise EngineCrash: the mid-generation crash fires at
                # the segment boundary, exactly where a cancel would land
                self.fault_injector.poll_segment(self.replica_id)
            return self._cancel or (cancel_cb is not None and cancel_cb())

        with NO_REGION if region is None else region("decode"):
            if self.speculative:
                _, dcaches, _ = self._run_prefill(
                    prompt_ids, prefill=self._draft_prefill,
                    params=self.draft_params)
                out = self._spec_decoder.decode(
                    self.params, self.draft_params, caches, dcaches, tok,
                    plen, max_new_tokens, eos_id=eos_id,
                    cancel_check=cancelled, on_segment=on_segment,
                    region=region)
            else:
                dec = self._decoder(segment_len or self.segment_len)
                out = dec.decode(self.params, caches, tok, plen,
                                 max_new_tokens, eos_id=eos_id,
                                 cancel_check=cancelled,
                                 on_segment=on_segment, region=region)
        self.served += 1
        self._cancel = False
        res = {"tokens": out["tokens"], "ttft_s": ttft,
               "service_s": time.monotonic() - t0,
               "cancelled": out["cancelled"], "segments": out["segments"]}
        if self.speculative:
            res["drafted"] = out["drafted"]
            res["accepted"] = out["accepted"]
            res["accept_rate"] = out["accepted"] / out["drafted"] \
                if out["drafted"] else None
        return res

    def generate_batch(self, prompts, max_new_tokens=32,
                       eos_id: Optional[int] = None) -> list:
        """Serial fallback so both engine classes share one batch API."""
        maxes = self._per_request_budgets(prompts, max_new_tokens)
        return [self.generate(ids, max_new_tokens=m, eos_id=eos_id)
                for ids, m in zip(prompts, maxes)]

    @staticmethod
    def _per_request_budgets(prompts, max_new_tokens) -> list:
        if np.isscalar(max_new_tokens):
            return [int(max_new_tokens)] * len(prompts)
        return [int(m) for m in max_new_tokens]

    def generate_reference(self, prompt_ids: np.ndarray,
                           max_new_tokens: int = 32,
                           eos_id: Optional[int] = None) -> dict:
        """Seed per-token Python loop (one host sync + dispatch per token).

        Kept in-tree as the equivalence oracle for the fused loop: same
        prefill, same stop-condition order, so token sequences must match
        bitwise (tests/test_generate.py).  ``top2[i]`` holds the largest
        and second-largest logit behind token ``i``: how near the greedy
        choice came to a tie.
        """
        import jax.numpy as jnp
        t0 = time.monotonic()
        logits, caches, plen = self._run_prefill(prompt_ids)
        row = np.asarray(logits)[0]
        tok = int(np.argmax(row))
        top2 = [_top2(row)]
        ttft = time.monotonic() - t0
        out = [tok]
        for _ in range(max_new_tokens - 1):
            if eos_id is not None and tok == eos_id:
                break
            if plen + len(out) >= self.max_len:
                break
            logits, caches = self._decode(
                self.params, caches, {"tokens": jnp.full((1, 1), tok, jnp.int32)})
            row = np.asarray(logits)[0]
            tok = int(np.argmax(row))
            top2.append(_top2(row))
            out.append(tok)
        self.served += 1
        return {"tokens": out, "ttft_s": ttft,
                "service_s": time.monotonic() - t0, "top2": top2}


class BatchedRealEngine(RealEngine):
    """Bounded-concurrency real decode: ``n_lanes`` concurrent requests
    under a KV-memory budget (serving/batching.py).

    Each lane is an independent ring-buffer cache stacked on a leading
    lane axis; one fused segment steps every live lane together
    (``serving.generate.LaneDecoder``), and segment boundaries are the
    join points where finished lanes retire and the manager back-fills
    from the caller's queue by re-prefilling into the vacant cache slot —
    continuous micro-batching with static cache shapes (no recompiles as
    the batch composition changes).

    Equivalence contract: under greedy decode, each request's token
    sequence is bitwise-equal to an independent ``generate_reference``
    run — including requests admitted mid-stream by back-fill
    (tests/test_batching.py).

    Admission is memory-aware and strictly policy-ordered: the next
    request (in the order the ``source`` yields them) is admitted only
    when its worst-case KV footprint — ``min(max_len, prompt + max_new)``
    ring slots at ``kv_bytes_per_token(cfg)`` — fits the budget; a head
    that does not fit blocks until lanes retire (no smaller request may
    bypass it).  ``budget_bytes=None`` sizes the budget to exactly
    ``n_lanes`` full rings, i.e. lane-count-limited.
    """

    def __init__(self, cfg, params=None, replica_id: int = 0, seed: int = 0,
                 max_len: int = 256, segment_len: int = 16,
                 n_lanes: int = 4, budget_bytes: Optional[int] = None,
                 draft_cfg=None, draft_params=None, draft_k: int = 0,
                 draft_seed: int = 0):
        from repro.serving.batching import kv_bytes_per_token
        from repro.serving.generate import (LaneDecoder,
                                            SpeculativeLaneDecoder)
        super().__init__(cfg, params=params, replica_id=replica_id,
                         seed=seed, max_len=max_len, segment_len=segment_len,
                         draft_cfg=draft_cfg, draft_params=draft_params,
                         draft_k=draft_k, draft_seed=draft_seed)
        self.n_lanes = int(n_lanes)
        self._bytes_per_token = kv_bytes_per_token(cfg)
        # a speculative lane carries the draft model's ring KV alongside
        # the target's — real memory, charged against the same budget
        self._draft_bytes_per_token = kv_bytes_per_token(draft_cfg) \
            if self.speculative else 0
        lane_bpt = self._bytes_per_token + self._draft_bytes_per_token
        self.budget_bytes = int(budget_bytes) if budget_bytes is not None \
            else self.n_lanes * max_len * max(1, lane_bpt)
        if self.speculative:
            self._lane_decoder = SpeculativeLaneDecoder(
                self.lm, self.draft_lm, self.draft_params, max_len,
                self.n_lanes, segment_len, draft_k=self.draft_k)
            # paged growth must cover every verify position a segment can
            # write: rounds x (K+1) slots, vs segment_len serial steps
            self._growth_span = self._lane_decoder.rounds * (self.draft_k + 1)
        else:
            self._lane_decoder = LaneDecoder(self.lm, max_len, self.n_lanes,
                                             segment_len)
            self._growth_span = segment_len
        self.lane_manager = None       # the most recent run's manager/stats
        self.dead_steps = 0            # lane-steps burned on stopped lanes
        self.drafted_total = 0         # draft positions proposed (this run)
        self.accepted_total = 0        # draft positions accepted (this run)

    def take_pending(self) -> list:
        """Drain the popped-but-not-admitted work items of the most recent
        ``run_lanes`` call (crash recovery: these left the caller's queue
        but never reached a lane, so an aborted run would lose them)."""
        items, self._pending_items = list(self._pending_items), []
        return items

    @property
    def accept_rate(self) -> Optional[float]:
        """Aggregate draft acceptance over the most recent run, or None
        before any draft position was proposed."""
        return self.accepted_total / self.drafted_total \
            if self.drafted_total else None

    def engine_stats(self) -> dict:
        """Wire-facing stats: adds dead-step and speculation accounting
        plus live lane occupancy (sidecar /healthz, /metrics)."""
        st = super().engine_stats()
        mgr = self.lane_manager
        st.update(dead_steps=self.dead_steps, lanes=self.n_lanes,
                  lanes_busy=len(mgr.busy_lanes()) if mgr is not None
                  else 0, drafted=self.drafted_total,
                  accepted=self.accepted_total,
                  accept_rate=self.accept_rate)
        return st

    def _accumulate_spec(self, mgr, dec) -> None:
        """Post-segment speculation accounting: per-lane and aggregate
        drafted/accepted counters, and the dead-step extension — wasted
        draft positions (drafted - accepted) burn lane time exactly like
        the masked compute of a stopped lane, so they fold into the same
        ``dead_steps`` figure the PR-5 trade-off reports."""
        if not self.speculative:
            return
        drafted, accepted = dec.last_drafted, dec.last_accepted
        for lane in mgr.busy_lanes():
            st = mgr.lanes[lane]
            st.drafted += int(drafted[lane])
            st.accepted += int(accepted[lane])
        d, a = int(drafted.sum()), int(accepted.sum())
        self.drafted_total += d
        self.accepted_total += a
        self.dead_steps += d - a
        mgr.stats["drafted"] = self.drafted_total
        mgr.stats["accepted"] = self.accepted_total
        mgr.stats["accept_rate"] = self.accept_rate

    # ----------------------------------------------------------- batch API
    def generate_batch(self, prompts, max_new_tokens=32,
                       eos_id: Optional[int] = None) -> list:
        """Decode a request list through the lanes; results in input order.

        ``max_new_tokens`` is a scalar or per-request sequence.  Returns
        one dict per request: {"tokens", "ttft_s", "service_s",
        "cancelled", "lane", "evictions"}.
        """
        maxes = self._per_request_budgets(prompts, max_new_tokens)
        n = len(prompts)
        results: list = [None] * n
        cursor = {"i": 0}

        def source(k: int) -> list:
            out = []
            while k > 0 and cursor["i"] < n:
                i = cursor["i"]
                cursor["i"] += 1
                out.append({"req_id": i, "ids": prompts[i],
                            "max_new": maxes[i], "meta": {"i": i}})
                k -= 1
            return out

        def on_finish(state, res):
            results[state.meta["i"]] = res

        self.run_lanes(source, on_finish, eos_id=eos_id)
        return results

    # -------------------------------------------------- lane-loop hook points
    # The paged engine (PagedBatchedEngine) reuses the whole run_lanes loop
    # and specializes only these: manager construction, the admission
    # check/commit (prefix-aware in pages), the prefill-and-insert step
    # (page scatter + extend prefill), the pre-segment hook (page growth /
    # preemption) and the post-release hook (block-table scrub).
    def _new_manager(self):
        from repro.serving.batching import KVBudget, LaneManager
        return LaneManager(self.n_lanes, KVBudget(self.budget_bytes),
                           self._bytes_per_token
                           + self._draft_bytes_per_token, self.max_len)

    def _head_fits(self, mgr, item, ids) -> bool:
        return mgr.can_admit(len(ids), item["max_new"])

    def _admit_item(self, mgr, lane: int, item, ids, t_admit, backfill: bool):
        return mgr.admit(lane, req_id=item["req_id"], prompt_len=len(ids),
                         max_new=item["max_new"],
                         tenant=item.get("tenant", "default"),
                         admit_t=t_admit, meta=item.get("meta"),
                         backfill=backfill)

    def _post_insert(self, group, first, plens, now, tok, plen, produced,
                     max_new, active) -> None:
        """Shared host-side bookkeeping once a claim group is prefilled
        and inserted: per-lane counters + the first (prefill) token."""
        for r, (st, lane, ids, mx) in enumerate(group):
            st.prompt_len = plens[r]
            st.ttft_s = now() - st.admit_t
            st.tokens = [int(first[r])]
            tok[lane] = int(first[r])
            plen[lane] = plens[r]
            produced[lane] = 1
            max_new[lane] = mx
            active[lane] = True

    def _insert_draft(self, dec, caches, lanes, ids_list):
        """Speculative only: prefill the draft model over the same ids
        (identical bucketing, so cache rows lay out like the target's)
        and drop the rows into the lanes' draft caches.  Resumed and
        prefix-hit requests take this same full prefill — the draft has
        no prefix cache, and its state only ever affects acceptance rate,
        never emitted tokens."""
        if not self.speculative:
            return caches
        _, dcache, _ = self._run_prefill_group(
            ids_list, pad_rows=self.n_lanes, prefill=self._draft_prefill,
            params=self.draft_params)
        return dec.insert_draft(caches, lanes, dcache)

    def _prefill_claims(self, mgr, dec, caches, claims, now, tok, plen,
                        produced, max_new, active):
        """Prefill admitted claims per bucket group (rows pad exactly as
        their solo prefill would, so per-lane results match the serial
        path bitwise) — one jit call + one lane insert per group."""
        groups: dict = {}
        for claim in claims:
            groups.setdefault(self.bucket(len(claim[2])), []).append(claim)
        for group in groups.values():
            logits, pcache, plens = self._run_prefill_group(
                [ids for _, _, ids, _ in group], pad_rows=self.n_lanes)
            first = np.argmax(np.asarray(logits), axis=-1)
            caches = dec.insert_lanes(
                caches, [lane for _, lane, _, _ in group], pcache)
            caches = self._insert_draft(
                dec, caches, [lane for _, lane, _, _ in group],
                [ids for _, _, ids, _ in group])
            self._post_insert(group, first, plens, now, tok, plen,
                              produced, max_new, active)
        return caches

    def _boundary_reset(self) -> None:
        """Start-of-segment-boundary hook (per outer loop iteration)."""

    def _pre_segment(self, mgr, dec, caches, tok, produced, plen, max_new,
                     active, dev, pending):
        """Hook before the segment launch.  Returns (caches, changed);
        ``changed`` means lanes were freed (the caller back-fills and
        re-runs the hook until it settles)."""
        return caches, False

    def _post_release(self, dec, caches, lanes):
        """Hook after lanes retire/evict (paged: scrub block tables so
        the released pages can never receive the lanes' dead writes)."""
        return caches

    def _result_tokens(self, state) -> list:
        return list(state.tokens)

    def _init_lanes(self, dec):
        """Lane-cache construction per run (paged: reuses the previous
        run's pools so the prefix cache keeps its contents)."""
        return dec.init_lanes()

    def _retain_caches(self, caches) -> None:
        """End-of-run hook: the paged engine stows the pools for the
        next run; the ring engine lets them be collected."""

    def run_lanes(self, source, on_finish, *, eos_id: Optional[int] = None,
                  cancel_check=None, now_fn=None) -> None:
        """Drive the lanes until ``source`` and all lanes drain.

        ``source(k)`` returns up to ``k`` work items (dicts with
        ``req_id``/``ids``/``max_new`` and optional ``tenant``/``meta``)
        in dispatch order — the server passes a closure over its policy
        queue so aging promotions are observed at every back-fill.
        ``on_finish(LaneState, result)`` fires as each request retires.
        ``cancel_check(LaneState) -> bool`` is polled at segment
        boundaries; a cancelled lane is evicted and reported with
        ``cancelled=True`` (§3.4 drain semantics, per lane).
        ``now_fn`` supplies admission/finish timestamps (defaults to
        wall clock; the server injects its virtual clock).
        """
        import jax.numpy as jnp
        now = now_fn if now_fn is not None else time.monotonic
        rec = self.recorder
        _ltrk = [f"replica{self.replica_id}/lane{i}"
                 for i in range(self.n_lanes)]
        mgr = self._new_manager()
        self.lane_manager = mgr
        self.dead_steps = 0
        self.drafted_total = 0
        self.accepted_total = 0
        dec = self._lane_decoder
        C = self.n_lanes
        caches = self._init_lanes(dec)
        # host-authoritative lane arrays; mirrored to device lazily (the
        # device copies persist across segments and are rebuilt only when
        # admission/eviction changes the lane composition — "dirty")
        tok = np.zeros(C, np.int32)
        produced = np.zeros(C, np.int32)
        plen = np.ones(C, np.int32)
        max_new = np.zeros(C, np.int32)
        active = np.zeros(C, bool)
        eos = jnp.asarray(-1 if eos_id is None else eos_id, jnp.int32)
        dev = {"d": None}               # (tok, produced, plen, max_new, act)
        pending: list = []              # popped but budget-blocked items
        # exposed for exception-safe callers: if a crash propagates out of
        # this method, items popped from the queue but not yet admitted to
        # a lane are recoverable via take_pending()
        self._pending_items = pending
        drained = {"source": False}

        def fill(backfill: bool = False) -> None:
            nonlocal caches
            free = mgr.free_lanes()
            # phase 1: claim admissible (item, lane) pairs under the
            # budget, in strict source order (a blocked head blocks all)
            claims = []
            while free:
                want = len(free) - len(pending)
                if want > 0 and not drained["source"]:
                    got = source(want)
                    if len(got) < want:
                        drained["source"] = True
                    pending.extend(got)
                if not pending:
                    break
                item = pending[0]
                ids = np.asarray(item["ids"], np.int64).reshape(-1)
                if not self._head_fits(mgr, item, ids):
                    # strict policy order: the head blocks, nothing bypasses
                    mgr.stats["blocked_on_budget"] += 1
                    break
                pending.pop(0)
                lane = free.pop(0)
                st = self._admit_item(mgr, lane, item, ids, now(), backfill)
                claims.append((st, lane, ids, item["max_new"]))
            if not claims:
                return
            # phase 2: prefill + lane insert (paged: page scatter / extend)
            caches = self._prefill_claims(mgr, dec, caches, claims, now,
                                          tok, plen, produced, max_new,
                                          active)
            if rec is not None:
                for st, lane, _, _ in claims:
                    rec.span("prefill", st.req_id, st.admit_t,
                             st.admit_t + max(st.ttft_s, 0.0),
                             track=_ltrk[lane])
            dev["d"] = None             # lane composition changed

        def finish(state, cancelled: bool, crashed: bool = False) -> None:
            t_fin = now()
            self.served += not cancelled
            if rec is not None:
                t0d = min(state.admit_t + max(state.ttft_s, 0.0), t_fin)
                rec.span("decode", state.req_id, t0d, t_fin,
                         track=_ltrk[state.lane])
            res = {
                "tokens": self._result_tokens(state), "cancelled": cancelled,
                "crashed": crashed,
                "ttft_s": state.ttft_s, "admit_t": state.admit_t,
                "finish_t": t_fin, "service_s": t_fin - state.admit_t,
                "lane": state.lane, "evictions": state.evictions}
            if self.speculative:
                res["drafted"] = state.drafted
                res["accepted"] = state.accepted
                res["accept_rate"] = state.accept_rate
            on_finish(state, res)

        inj = self.fault_injector
        fill()
        # `pending` in the condition: growth preemption (paged) can empty
        # every lane while the just-preempted head sits deferred for the
        # boundary — the next iteration lifts the deferral and re-admits
        # (an idle manager always admits its head, so this terminates)
        while active.any() or pending:
            self._boundary_reset()
            # segment boundary: collect client disconnects and injected
            # lane crashes, then evict + back-fill in one pass.  A
            # whole-engine crash (poll_segment) raises out of run_lanes;
            # the server requeues busy lanes + pending items.
            evictions = []                  # (lane, crashed)
            if cancel_check is not None:
                for lane in mgr.busy_lanes():
                    if cancel_check(mgr.lanes[lane]):
                        evictions.append((lane, False))
            if inj is not None:
                inj.poll_segment(self.replica_id)
                spec = inj.lane_crash_due(self.replica_id)
                while spec is not None:
                    taken = {lane for lane, _ in evictions}
                    busy = [ln for ln in mgr.busy_lanes()
                            if ln not in taken]
                    if not busy:
                        break
                    victim = spec.lane if spec.lane in busy else busy[0]
                    evictions.append((victim, True))
                    spec = inj.lane_crash_due(self.replica_id)
            if evictions:
                for lane, crashed in evictions:
                    st = mgr.evict(lane)
                    active[lane] = False
                    finish(st, cancelled=True, crashed=crashed)
                if dev["d"] is not None:
                    tok = np.array(dev["d"][0])       # refresh host mirror
                dev["d"] = None
                caches = self._post_release(
                    dec, caches, [lane for lane, _ in evictions])
                fill(backfill=True)
                if not active.any():
                    continue
            # paged: grow block tables for the coming segment, preempting
            # the youngest lanes on pool exhaustion; each preemption frees
            # a lane, so back-fill and re-settle until stable
            while True:
                caches, changed = self._pre_segment(
                    mgr, dec, caches, tok, produced, plen, max_new,
                    active, dev, pending)
                if not changed:
                    break
                fill(backfill=True)
            if not active.any():
                # every lane drained while the head sat deferred (it was
                # preempted in the same boundary the last lanes retired).
                # The deferral was lifted at the top of this iteration and
                # an idle manager admits its head, so this either admits
                # (progress) or pending is empty (the loop exits)
                fill(backfill=True)
                continue
            if dev["d"] is None:
                dev["d"] = (jnp.asarray(tok), jnp.asarray(produced),
                            jnp.asarray(plen), jnp.asarray(max_new),
                            jnp.asarray(active))
            tok_d, produced_d, plen_d, max_new_d, active_d = dev["d"]
            t_seg0 = now() if rec is not None else 0.0
            new_toks, tok_d, produced_d, caches, stopped, produced, dead = \
                dec.run_segment(self.params, caches, tok_d, produced_d,
                                plen_d, max_new_d, eos, active_d,
                                produced_before=produced)
            dev["d"] = (tok_d, produced_d, plen_d, max_new_d, active_d)
            self.dead_steps += dead
            self._accumulate_spec(mgr, dec)
            mgr.stats["dead_steps"] = self.dead_steps
            if rec is not None:
                t_seg1 = now()
                for lane in mgr.busy_lanes():
                    rec.span("decode_segment", mgr.lanes[lane].req_id,
                             t_seg0, t_seg1, track=_ltrk[lane])
            retired = False
            released = []
            for lane in mgr.busy_lanes():
                st = mgr.lanes[lane]
                st.tokens.extend(new_toks[lane])
                st.produced = int(produced[lane])
                if stopped[lane]:
                    st = mgr.retire(lane)
                    active[lane] = False
                    retired = True
                    released.append(lane)
                    finish(st, cancelled=False)
            if retired:
                # host tok mirror must be current before fill mutates it
                tok = np.array(tok_d)
                dev["d"] = None
                caches = self._post_release(dec, caches, released)
                fill(backfill=True)
        self._retain_caches(caches)


class PagedBatchedEngine(BatchedRealEngine):
    """Micro-batching over a block-paged KV pool with prefix reuse.

    Same lane loop, stop semantics and bitwise-token contract as
    :class:`BatchedRealEngine`, with the memory subsystem swapped
    (serving/paging.py):

    * **Admission charges actual footprint** — the prompt's pages, not
      the worst-case ring.  The same byte budget therefore admits more
      lanes when memory binds (the phantom-byte recovery the paging
      bench measures).
    * **Prefix reuse** — full prompt pages are content-addressed after
      prefill; a later prompt sharing the prefix re-acquires the cached
      pages and prefills only its suffix (extend prefill), cutting both
      memory and prefill compute.
    * **Page growth + preemption** — decode allocates pages as the
      sequence crosses page boundaries (one segment's worth ahead).  On
      exhaustion the youngest lane is preempted: its pages are freed and
      the request re-enters the pending list, resuming later via the
      PR-4 rule (re-prefill prompt + generated prefix), so its tokens
      stay bitwise-equal to an uninterrupted run.  The oldest lane is
      never preempted and the pool always holds one full sequence, so
      the loop cannot deadlock.

    The allocator persists across ``run_lanes`` calls — the prefix cache
    (LRU-parked pages) survives between drains, like a production
    server's; ``reset_transient`` drops only live references.
    """

    def __init__(self, cfg, params=None, replica_id: int = 0, seed: int = 0,
                 max_len: int = 256, segment_len: int = 16,
                 n_lanes: int = 4, budget_bytes: Optional[int] = None,
                 page_size: int = 16, draft_cfg=None, draft_params=None,
                 draft_k: int = 0, draft_seed: int = 0):
        import jax
        import jax.numpy as jnp
        from repro.serving.generate import (PagedLaneDecoder,
                                            SpeculativePagedLaneDecoder)
        from repro.serving.paging import BlockAllocator, pages_for
        super().__init__(cfg, params=params, replica_id=replica_id,
                         seed=seed, max_len=max_len, segment_len=segment_len,
                         n_lanes=n_lanes, budget_bytes=budget_bytes,
                         draft_cfg=draft_cfg, draft_params=draft_params,
                         draft_k=draft_k, draft_seed=draft_seed)
        if not _pure_attention(cfg):
            raise ValueError("block-paged KV needs a pure-attention stack "
                             f"(got pattern {cfg.block_pattern})")
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} not a multiple of "
                             f"page_size {page_size}")
        self.page_size = int(page_size)
        page_bytes = self.page_size * max(1, self._bytes_per_token)
        # a speculative lane's draft ring, denominated in target pages
        # (ceil): anonymous pages the admission layer charges per lane
        self._overhead_pages = -(-max_len * self._draft_bytes_per_token
                                 // page_bytes) if self.speculative else 0
        # same byte budget as the worst-case engine, denominated in pages
        # (floor); never below one full sequence (plus its draft
        # overhead) so a solo lane always fits
        self.n_pages = max(pages_for(max_len, self.page_size)
                           + self._overhead_pages,
                           self.budget_bytes // page_bytes)
        self.allocator = BlockAllocator(self.n_pages, self.page_size)
        if self.speculative:
            self._lane_decoder = SpeculativePagedLaneDecoder(
                self.lm, self.draft_lm, self.draft_params, max_len,
                self.n_lanes, segment_len, n_pages=self.n_pages + 1,
                page_size=self.page_size, draft_k=self.draft_k)
        else:
            self._lane_decoder = PagedLaneDecoder(
                self.lm, max_len, self.n_lanes, segment_len,
                n_pages=self.n_pages + 1, page_size=self.page_size)
        self._deferred: set = set()    # req_ids preempted at this boundary
        self._caches = None            # pools retained between runs
        # extend prefill: suffix tokens appended onto a gathered prefix
        # cache.  One jit; retraces per (suffix bucket, prefix extent).
        self._prefill_ext = jax.jit(
            lambda p, toks, pl, pcaches, fill_to: self.lm.prefill(
                p, {"tokens": toks}, prompt_len=pl, caches=pcaches,
                fill_to=fill_to))

    def engine_stats(self) -> dict:
        """Adds paged-pool page states (free/cached/held) and prefix-hit
        accounting to the batched stats."""
        st = super().engine_stats()
        st["pages"] = self.allocator.page_states()
        st["prefix_hits"] = self.allocator.stats["prefix_hits"]
        st["prefix_hit_pages"] = self.allocator.stats["prefix_hit_pages"]
        return st

    # ------------------------------------------------------------ lane hooks
    def _new_manager(self):
        from repro.serving.paging import PagedLaneManager
        self.allocator.reset_transient()   # drop refs leaked by a crash
        self._deferred = set()
        return PagedLaneManager(self.n_lanes, self.allocator,
                                self._bytes_per_token, self.max_len,
                                overhead_pages=self._overhead_pages)

    def _init_lanes(self, dec):
        # reuse the previous run's pools: the LRU-parked prefix pages
        # keep their KV, so cross-run prefix hits serve real contents.
        # If the pools are gone (first run, or the previous run crashed
        # before retaining them), the content cache must go with them.
        caches, self._caches = self._caches, None
        if caches is None:
            self.allocator.drop_cache()
            caches = dec.init_lanes()
        return caches

    def _retain_caches(self, caches) -> None:
        self._caches = caches

    def _boundary_reset(self) -> None:
        # a preempted request may be re-admitted at the NEXT boundary;
        # deferring it for the current one prevents admit/preempt churn
        self._deferred = set()

    def _head_fits(self, mgr, item, ids) -> bool:
        if item["req_id"] in self._deferred:
            return False
        # a preempted request re-admits on its FULL remaining footprint
        # (prefill + every growth page), not just the prefill pages: the
        # re-prefill is paid work, and admitting it into a pool that
        # cannot also hold its growth just preempts it again before it
        # produces a token — an admit/re-prefill/preempt cycle that burns
        # wall-clock without progress (the DES mirror makes the same
        # charge for resumed jobs)
        eff_len = len(ids)
        if item.get("_evictions", 0) > 0:
            eff_len += int(item["max_new"])
        return mgr.can_admit(eff_len, item["max_new"], ids=ids)

    def _admit_item(self, mgr, lane: int, item, ids, t_admit, backfill: bool):
        st = mgr.admit(lane, req_id=item["req_id"], prompt_len=len(ids),
                       max_new=item["max_new"],
                       tenant=item.get("tenant", "default"),
                       admit_t=t_admit, meta=item.get("meta"),
                       backfill=backfill, ids=ids)
        st.evictions = item.get("_evictions", 0)
        st.meta["_ids"] = ids
        st.meta["_resume_tokens"] = list(item.get("_resume_tokens", ()))
        return st

    def _result_tokens(self, state) -> list:
        return list(state.meta.get("_resume_tokens", ())) \
            + list(state.tokens)

    def _prefill_claims(self, mgr, dec, caches, claims, now, tok, plen,
                        produced, max_new, active):
        from repro.serving.generate import bucket_for
        from repro.serving.paging import pages_for
        ps = self.page_size
        P = self.max_len // ps
        cold = [c for c in claims if c[0].prefix_len == 0]
        warm = [c for c in claims if c[0].prefix_len > 0]
        # cold prompts: grouped full prefill (identical to the base
        # engine), then scatter the prompt pages into the pool
        groups: dict = {}
        for claim in cold:
            groups.setdefault(bucket_for(len(claim[2]), self.buckets),
                              []).append(claim)
        for group in groups.values():
            logits, pcache, plens = self._run_prefill_group(
                [ids for _, _, ids, _ in group], pad_rows=self.n_lanes)
            first = np.argmax(np.asarray(logits), axis=-1)
            k = len(group)
            bt_rows = np.zeros((k, P), np.int32)
            tgt = np.zeros((k, P), np.int32)   # pcache padded to max_len
            for r, (st, lane, ids, mx) in enumerate(group):
                bt_rows[r, :len(st.pages)] = st.pages
                npp = pages_for(len(ids), ps)
                tgt[r, :npp] = st.pages[:npp]
            caches = dec.insert_paged(
                caches, [lane for _, lane, _, _ in group], pcache,
                bt_rows, tgt)
            caches = self._insert_draft(
                dec, caches, [lane for _, lane, _, _ in group],
                [ids for _, _, ids, _ in group])
            self._post_insert(group, first, plens, now, tok, plen,
                              produced, max_new, active)
            for st, lane, ids, _ in group:
                mgr.register_prompt(lane, ids)
        # prefix hits: gather the cached pages, prefill only the suffix
        for claim in warm:
            caches = self._extend_prefill(mgr, dec, caches, claim, now,
                                          tok, plen, produced, max_new,
                                          active)
        return caches

    def _extend_prefill(self, mgr, dec, caches, claim, now, tok, plen,
                        produced, max_new, active):
        import jax.numpy as jnp
        from repro.serving.generate import bucket_for
        from repro.serving.paging import pages_for
        st, lane, ids, mx = claim
        ps = self.page_size
        P = self.max_len // ps
        n_match = st.prefix_len // ps
        Bf = bucket_for(len(ids), self.buckets)
        nf = -(-Bf // ps)
        pre_pages = np.zeros(nf, np.int32)
        pre_pages[:n_match] = st.pages[:n_match]
        pre_cache = dec.gather_prefix(caches, pre_pages, st.prefix_len)
        Ls = len(ids) - st.prefix_len
        Bs = min(bucket_for(Ls, self.buckets), nf * ps - st.prefix_len)
        toks = np.zeros((1, Bs), np.int32)
        toks[0, :Ls] = ids[st.prefix_len:]
        logits, pcache = self._prefill_ext(
            self.params, jnp.asarray(toks), jnp.asarray(Ls, jnp.int32),
            pre_cache, jnp.asarray(len(ids), jnp.int32))
        first = np.argmax(np.asarray(logits), axis=-1)
        npp = pages_for(len(ids), ps)
        bt_rows = np.zeros((1, P), np.int32)
        bt_rows[0, :len(st.pages)] = st.pages
        tgt = np.zeros((1, nf), np.int32)
        # only the NEW pages are scattered; the matched prefix already
        # lives in the pool (and may be shared — it must not be rewritten)
        tgt[0, n_match:npp] = st.pages[n_match:npp]
        caches = dec.insert_paged(caches, [lane], pcache, bt_rows, tgt)
        # prefix hits still do a FULL draft prefill: the draft side has
        # no prefix cache (and cannot corrupt tokens, only acceptance)
        caches = self._insert_draft(dec, caches, [lane], [ids])
        self._post_insert([claim], first, [len(ids)], now, tok, plen,
                          produced, max_new, active)
        mgr.register_prompt(lane, ids)
        return caches

    def _post_release(self, dec, caches, lanes):
        # scrub the released lanes' block tables: their dead writes (the
        # lane keeps stepping while inactive) must land on the trash
        # page, never on a page the allocator may hand to someone else
        P = self.max_len // self.page_size
        rows = np.zeros((len(lanes), P), np.int32)
        return dec.set_bt(caches, list(lanes), rows)

    def _pre_segment(self, mgr, dec, caches, tok, produced, plen, max_new,
                     active, dev, pending):
        from repro.serving.paging import pages_for
        ps = self.page_size
        P = self.max_len // ps
        # speculative segments write verify positions ahead of the fill
        # level (rounds x (K+1) slots); an unallocated page would silently
        # route those writes to the trash page and lose real KV
        K = self._growth_span
        changed = False
        new_rows: dict = {}                   # lane -> block-table row
        order = sorted(mgr.busy_lanes(),
                       key=lambda ln: mgr.lanes[ln].meta["_admit_seq"])
        for lane in order:
            st = mgr.lanes[lane]
            if st is None:                    # preempted earlier this pass
                continue
            # pages for every slot the coming segment can write:
            # the next write lands at plen + produced - 1
            target = min(self.max_len,
                         int(plen[lane]) + int(produced[lane]) + K - 1)
            need = pages_for(target, ps)
            if need <= len(st.pages):
                continue
            while not mgr.grow(lane, need):
                seq = st.meta["_admit_seq"]
                younger = [l for l in mgr.busy_lanes()
                           if mgr.lanes[l].meta["_admit_seq"] > seq]
                victim = max(younger, key=lambda l:
                             mgr.lanes[l].meta["_admit_seq"]) \
                    if younger else lane
                self._preempt_lane(mgr, victim, tok, produced, active,
                                   dev, pending)
                new_rows[victim] = np.zeros(P, np.int32)
                changed = True
                if victim == lane:
                    break
            if mgr.lanes[lane] is st:         # grown (not self-preempted)
                row = np.zeros(P, np.int32)
                row[:len(st.pages)] = st.pages
                new_rows[lane] = row
        if new_rows:
            idx = sorted(new_rows)
            caches = dec.set_bt(caches, idx,
                                np.stack([new_rows[i] for i in idx]))
        return caches, changed

    def _preempt_lane(self, mgr, lane, tok, produced, active, dev,
                      pending) -> None:
        """Free a lane's pages mid-flight and requeue the request at the
        head of the pending list (it was admitted earliest).  The resume
        item re-prefills prompt + generated prefix — the PR-4 rule — so
        the final token sequence matches an uninterrupted run."""
        if dev["d"] is not None:
            tok[:] = np.array(dev["d"][0])    # refresh host mirrors
            produced[:] = np.array(dev["d"][1])
            dev["d"] = None
        st = mgr.preempt(lane)
        active[lane] = False
        meta = {k: v for k, v in st.meta.items()
                if k not in ("_admit_seq", "_ids", "_resume_tokens")}
        item = {
            "req_id": st.req_id,
            "ids": np.concatenate([
                np.asarray(st.meta["_ids"], np.int64).reshape(-1),
                np.asarray(st.tokens, np.int64)]),
            "max_new": st.max_new - len(st.tokens),
            "tenant": st.tenant, "meta": meta,
            "_evictions": st.evictions,
            "_resume_tokens": st.meta["_resume_tokens"] + list(st.tokens),
        }
        pending.insert(0, item)
        self._deferred.add(st.req_id)
