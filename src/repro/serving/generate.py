"""Fused on-device greedy generation: segmented ``lax.while_loop`` decode.

The seed decode loop (kept as ``RealEngine.generate_reference``) runs one
jitted ``decode_step`` per token and syncs to host every step — ``np.argmax``
on the logits plus a re-upload of the sampled token — so per-token cost on
small models is dispatch latency, not compute.  :class:`FusedDecoder`
replaces it with a *segmented* device loop:

* one jitted call runs up to ``segment_len`` decode steps in a
  ``lax.while_loop`` whose carry holds the current token, the KV caches and
  the emitted-token buffer — tokens never leave the device inside a segment;
* the EOS / ``max_len`` / ``max_new`` stop condition is evaluated on device
  in the loop predicate, mirroring the oracle's Python ``break``s exactly
  (same check order, so token sequences are bitwise-comparable);
* the KV caches are **donated** into the segment call
  (``donate_argnums``), so on backends with donation support the ring
  buffers update in place instead of being copied once per call;
* the host syncs once per segment to read the emitted tokens and check the
  engine's cancel flag (§3.4 drain semantics: a disconnect observed between
  segments stops generation at the segment boundary, freeing the serial
  dispatch slot within ``segment_len`` tokens).

Dispatch overhead is therefore amortized to ``1/segment_len`` of the seed
loop's; ``benchmarks/serve_bench.py`` measures the ratio and writes it to
``BENCH_serve.json``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.observability import NO_REGION


class FusedDecoder:
    """Device-resident segmented greedy decoder for one ``LM``.

    One instance per (model, max_len, segment_len); the segment function is
    compiled once per cache shape (i.e. per cache capacity x batch size).
    """

    def __init__(self, lm, max_len: int, segment_len: int = 16):
        assert segment_len >= 1
        self.lm = lm
        self.max_len = max_len
        self.segment_len = segment_len
        self._segment = jax.jit(self._segment_impl, donate_argnums=(1,))

    def _segment_impl(self, params, caches, tok, produced, prompt_len,
                      max_new, eos):
        """Run up to ``segment_len`` decode steps on device.

        tok: () int32 last emitted token; produced: () int32 tokens emitted
        so far (including the prefill token); eos: () int32 (-1 = disabled).
        Returns (buf (K,) int32 with -1 padding, tok, produced, caches,
        stopped) — ``stopped`` True when the generation-level stop condition
        holds, i.e. the host should not launch another segment.
        """
        K = self.segment_len
        max_len = self.max_len
        buf0 = jnp.full((K,), -1, jnp.int32)

        def live(tok, produced):
            # The oracle's break conditions, in order: EOS, cache/window
            # budget, request budget.
            return ((tok != eos)
                    & (prompt_len + produced < max_len)
                    & (produced < max_new))

        def cond(c):
            i, tok, produced, _, _ = c
            return (i < K) & live(tok, produced)

        def body(c):
            i, tok, produced, caches, buf = c
            logits, caches = self.lm.decode_step(
                params, caches, {"tokens": tok.reshape(1, 1)})
            tok = jnp.argmax(logits[0]).astype(jnp.int32)
            buf = jax.lax.dynamic_update_slice(buf, tok[None], (i,))
            return i + 1, tok, produced + 1, caches, buf

        _, tok, produced, caches, buf = jax.lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), tok, produced, caches, buf0))
        return buf, tok, produced, caches, ~live(tok, produced)

    def decode(self, params, caches, first_token: int, prompt_len: int,
               max_new_tokens: int, eos_id: Optional[int] = None,
               cancel_check=None, on_segment=None, region=None) -> dict:
        """Greedy-decode from a prefilled cache.

        ``first_token`` is the prefill argmax (already emitted).  Returns
        {"tokens": [first_token, ...], "cancelled": bool, "segments": int,
        "caches": final cache pytree}.

        ``on_segment(new_tokens)`` fires at every host sync with the
        tokens emitted since the previous call — the prefill token before
        the first segment, then one call per segment.  This is the SSE
        streaming hook: segment boundaries are the only points where
        tokens reach the host, so they are the natural flush granularity
        for the sidecar (and the same join points where cancellation and
        injected crashes land).  Exceptions from the callback propagate —
        emission is part of serving the request.

        ``region(name, **args)`` (a recorder's ``region`` bound to the
        request and track; None when untraced) times the host work of
        the loop: ``decode_poll`` (``cancel_check``), and per segment a
        ``decode_segment`` holding ``decode_dispatch`` (the jitted call,
        until it returns), ``decode_sync`` (the reads of ``produced`` and
        the token buffer), ``decode_emit`` (``on_segment``) and
        ``decode_stop`` (the read of ``stopped``).
        """
        out = [int(first_token)]
        if on_segment is not None:
            with NO_REGION if region is None else region("decode_emit"):
                on_segment([int(first_token)])
        tok = jnp.asarray(first_token, jnp.int32)
        produced = jnp.asarray(1, jnp.int32)
        plen = jnp.asarray(prompt_len, jnp.int32)
        max_new = jnp.asarray(max_new_tokens, jnp.int32)
        eos = jnp.asarray(-1 if eos_id is None else eos_id, jnp.int32)
        K = self.segment_len
        steps = min(max_new_tokens, self.max_len - prompt_len) - 1
        cancelled = False
        segments = 0
        # The first segment's predicate replays the oracle's post-prefill
        # checks, so a request that is already complete runs zero steps.
        while True:
            if cancel_check is not None:
                with NO_REGION if region is None else region("decode_poll"):
                    cancelled = bool(cancel_check())
                if cancelled:
                    break
            with NO_REGION if region is None else region(
                    "decode_segment", seg=segments, plen=prompt_len,
                    first_step=segments * K,
                    steps=max(0, min(K, steps - segments * K))):
                with NO_REGION if region is None \
                        else region("decode_dispatch"):
                    buf, tok, produced, caches, stopped = self._segment(
                        params, caches, tok, produced, plen, max_new, eos)
                segments += 1
                with NO_REGION if region is None else region("decode_sync"):
                    n_new = int(produced) - len(out)  # one sync per segment
                    buf_np = np.asarray(buf)
                new = [int(x) for x in buf_np[:n_new]]
                out.extend(new)
                if on_segment is not None and new:
                    with NO_REGION if region is None \
                            else region("decode_emit"):
                        on_segment(new)
                # the stop flag is read after the emit: the event loop can
                # then write the delta while this thread waits on the read
                with NO_REGION if region is None else region("decode_stop"):
                    stop = bool(stopped)
            if stop:
                break
        return {"tokens": out, "cancelled": cancelled, "segments": segments,
                "caches": caches}


class SpeculativeDecoder:
    """Serial draft-verify greedy decoder (speculative decoding, B=1).

    Each *round* runs the small draft model ``draft_k`` steps to propose a
    token chain, then scores the pending token plus the whole chain with
    ONE multi-position target forward (``LM.verify_step``) and accepts the
    longest prefix of drafts that match the target's own greedy argmaxes.
    Because every emitted token is a target argmax conditioned on the
    accepted prefix, the token sequence is **bitwise-equal** to the
    non-speculative fused/serial greedy reference — speculation changes
    how many target dispatches the sequence costs, never its contents.

    Round semantics (greedy accept-longest-prefix + bonus token):

    * verify feeds ``[pending, d_1..d_K]`` at fill levels ``t..t+K`` and
      takes target argmaxes ``a_0..a_K``;
    * ``m`` = longest prefix with ``d_i == a_{i-1}``; the round emits
      ``a_0..a_min(m, caps)`` (so a full match emits K+1 tokens — the
      K accepted drafts' successors plus the *bonus* ``a_K``), truncated
      by the serial stop rules (EOS inside the block, ring capacity,
      request budget) in exactly the oracle's check order;
    * commit advances both caches' fill levels to the accepted extent —
      rejected drafts roll back by simply **not advancing** ``t`` (stale
      KV past the fill level is masked and overwritten in write order
      later), so rollback costs no recompilation and no cleanup pass;
    * when the round fully accepts, the draft cache is one token short
      (it never consumed ``d_K``) — the next round's *catch-up step*
      feeds that tail token first.  Lanes without a tail dummy-feed: the
      write at the frozen slot is overwritten by the next real write and
      step-0 logits are never used.

    A live round always emits >= 1 token (``a_0`` costs the same target
    dispatch a serial step would), so all-rejected rounds still progress.

    Requires a pure-attention stack (the verify forward is an attention-
    cache operation) and a shared vocabulary between draft and target.
    """

    def __init__(self, lm, draft_lm, max_len: int, draft_k: int):
        if draft_k < 1:
            raise ValueError("draft_k must be >= 1 (K=0 is the fused path)")
        if lm.cfg.vocab_size != draft_lm.cfg.vocab_size:
            raise ValueError(
                f"draft/target vocab mismatch: {draft_lm.cfg.vocab_size} "
                f"vs {lm.cfg.vocab_size}")
        self.lm = lm
        self.draft_lm = draft_lm
        self.max_len = max_len
        self.draft_k = int(draft_k)
        self._round = jax.jit(self._round_impl, donate_argnums=(2, 3))

    def _round_impl(self, params, draft_params, caches, dcaches, tok,
                    produced, has_tail, tail, plen, max_new, eos):
        """One draft-verify-commit round, fully on device.

        Scalar carries: ``tok`` pending token, ``produced`` emitted count,
        ``has_tail``/``tail`` the draft catch-up state.  Returns
        (emit (K+1,) -1-padded, n_emit, tok, produced, has_tail, tail,
        caches, dcaches, stopped).
        """
        K = self.draft_k
        # --- draft phase: catch-up step + K chain steps -----------------
        d0 = [c["t"] for c in dcaches]
        feed0 = jnp.where(has_tail, tail, tok)
        _, dcaches = self.draft_lm.decode_step(
            draft_params, dcaches, {"tokens": feed0.reshape(1, 1)})
        ht = has_tail.astype(jnp.int32)
        dcaches = tuple({**c, "t": t0 + ht} for c, t0 in zip(dcaches, d0))

        def dstep(carry, _):
            cur, dc = carry
            lg, dc = self.draft_lm.decode_step(
                draft_params, dc, {"tokens": cur.reshape(1, 1)})
            nxt = jnp.argmax(lg[0]).astype(jnp.int32)
            return (nxt, dc), nxt

        (_, dcaches), d = jax.lax.scan(dstep, (tok, dcaches), None, length=K)

        # --- verify: one multi-position target forward ------------------
        feed = jnp.concatenate([tok[None], d])             # (K+1,)
        base_t = caches[0]["t"][0]                         # pre-round fill
        vlog, caches = self.lm.verify_step(params, caches,
                                           {"tokens": feed[None]})
        a = jnp.argmax(vlog[0], axis=-1).astype(jnp.int32)  # (K+1,)

        # --- acceptance: longest matching prefix + oracle stop order ----
        ok = (d == a[:K]).astype(jnp.int32)
        m_chain = jnp.cumprod(ok).sum()
        cap = jnp.minimum(m_chain + 1,
                          jnp.minimum(self.max_len - plen - produced,
                                      max_new - produced))
        idx = jnp.arange(K + 1, dtype=jnp.int32)
        is_eos = (a == eos) & (idx < cap)
        n_emit = jnp.where(is_eos.any(),
                           jnp.argmax(is_eos).astype(jnp.int32) + 1, cap)

        # --- commit ------------------------------------------------------
        emit = jnp.where(idx < n_emit, a, -1)
        new_tok = a[n_emit - 1]
        produced = produced + n_emit
        caches = tuple({**c, "t": jnp.full_like(c["t"], base_t + n_emit)}
                       for c in caches)
        n_keep = jnp.minimum(n_emit, K)
        dcaches = tuple({**c, "t": jnp.full_like(c["t"], base_t + n_keep)}
                        for c in dcaches)
        full = n_emit == K + 1
        stopped = ~((new_tok != eos)
                    & (plen + produced < self.max_len)
                    & (produced < max_new))
        return (emit, n_emit, new_tok, produced, full, d[K - 1], caches,
                dcaches, stopped)

    def decode(self, params, draft_params, caches, dcaches,
               first_token: int, prompt_len: int, max_new_tokens: int,
               eos_id: Optional[int] = None, cancel_check=None,
               on_segment=None, region=None) -> dict:
        """Greedy-decode from prefilled target + draft caches.

        Mirrors :meth:`FusedDecoder.decode` (same result keys, same
        cancel/stream join points — here every round is a segment and
        ``region`` times each as a ``decode_segment``), plus
        ``drafted``/``accepted`` counters (``accepted / drafted`` is the
        observed acceptance rate the admission layer feeds back into its
        effective-service-time key).
        """
        K = self.draft_k
        out = [int(first_token)]
        if on_segment is not None:
            on_segment([int(first_token)])
        tok = jnp.asarray(first_token, jnp.int32)
        produced = jnp.asarray(1, jnp.int32)
        has_tail = jnp.asarray(False)
        tail = jnp.asarray(0, jnp.int32)
        plen = jnp.asarray(prompt_len, jnp.int32)
        max_new = jnp.asarray(max_new_tokens, jnp.int32)
        eos = jnp.asarray(-1 if eos_id is None else eos_id, jnp.int32)
        cancelled = False
        rounds = drafted = accepted = 0
        # host-side live check replays the oracle's post-prefill stop
        # order, so an already-complete request runs zero rounds
        tok_h, produced_h = int(first_token), 1
        while ((eos_id is None or tok_h != eos_id)
               and prompt_len + produced_h < self.max_len
               and produced_h < max_new_tokens):
            if cancel_check is not None and cancel_check():
                cancelled = True
                break
            with NO_REGION if region is None else region(
                    "decode_segment", seg=rounds):
                (emit, n_emit, tok, produced, has_tail, tail, caches,
                 dcaches, stopped) = self._round(
                    params, draft_params, caches, dcaches, tok, produced,
                    has_tail, tail, plen, max_new, eos)
                rounds += 1
                n = int(n_emit)                  # one host sync per round
                new = [int(x) for x in np.asarray(emit)[:n]]
                out.extend(new)
                drafted += K
                accepted += n - 1
                if on_segment is not None and new:
                    on_segment(new)
                stop = bool(stopped)
            tok_h = new[-1]
            produced_h += n
            if stop:
                break
        return {"tokens": out, "cancelled": cancelled, "segments": rounds,
                "caches": caches, "draft_caches": dcaches,
                "drafted": drafted, "accepted": accepted}


class LaneDecoder:
    """Lane-batched segmented greedy decoder: ``n_lanes`` concurrent
    requests, one fused ``lax.while_loop`` per segment.

    Each lane is an independent single-request decode riding the model's
    **native batch axis**: the attention caches hold per-sequence ring
    fill levels (``t`` as a (lanes,) vector — models/attention.py), so
    lanes prefilled at different prompt lengths write their next KV at
    different ring slots, take their own RoPE positions and mask their
    own attention windows inside one natively batched ``decode_step``
    (native batching beats a vmap-of-B=1 formulation ~1.5x on CPU — the
    lifted ``(lanes, 1, 1, ...)`` shapes defeat XLA's batched-dot
    kernels).  Per lane the arithmetic is exactly the B=1 computation of
    the serial path, so per-lane token sequences are bitwise-equal to
    independent :class:`FusedDecoder` runs (greedy argmax;
    tests/test_batching.py).

    Segment semantics mirror :class:`FusedDecoder`:

    * the per-lane stop predicate (EOS / ``max_len`` ring budget /
      ``max_new`` request budget) is evaluated on device; a stopped lane
      keeps its token counters frozen (masked ``where`` updates) while
      the surviving lanes continue — its cache slots receive dead writes
      that never reach another lane and that the back-fill prefill
      overwrites wholesale;
    * the segment ends after ``segment_len`` steps or when every lane has
      stopped, and the host syncs once to read the per-lane token buffer;
    * segment boundaries are the **join points**: the host retires
      finished lanes and back-fills vacant cache slots via
      :meth:`insert_lane` (a fresh prefill dropped in at the lane index),
      so the batch composition changes with no recompilation — cache
      shapes are static in ``n_lanes``.
    """

    def __init__(self, lm, max_len: int, n_lanes: int, segment_len: int = 16):
        assert segment_len >= 1 and n_lanes >= 1
        self.lm = lm
        self.max_len = max_len
        self.n_lanes = n_lanes
        self.segment_len = segment_len
        self._segment = jax.jit(self._segment_impl, donate_argnums=(1,))

    # ------------------------------------------------------------ lane admin
    def init_lanes(self):
        """Zero caches for ``n_lanes`` sequences, with the attention fill
        levels expanded from the shared scalar to per-lane vectors."""
        caches = self.lm.init_cache(self.n_lanes, self.max_len)
        out = []
        for c in caches:
            if isinstance(c, dict) and "t" in c:
                c = dict(c)
                c["t"] = jnp.zeros(c["t"].shape + (self.n_lanes,),
                                   c["t"].dtype)
            out.append(c)
        return tuple(out)

    def insert_lane(self, lanes, lane: int, cache):
        """Drop a freshly prefilled (B=1) cache pytree into slot ``lane``.

        Batched leaves take the prefill's batch row; the per-lane fill
        level takes the prefill's scalar ``t``.  Shapes must match the
        per-lane slice exactly (prefill with ``pad_to=max_len``), so
        back-filling a retired lane re-uses the compiled segment
        program."""
        def put(big, one):
            if one.ndim == big.ndim:           # (rep, 1, ...) batch leaf
                return big.at[:, lane].set(one[:, 0])
            return big.at[:, lane].set(one)    # (rep,) -> (rep, lanes) fill
        return jax.tree.map(put, lanes, cache)

    def insert_lanes(self, lanes, lane_idx, cache):
        """Batched :meth:`insert_lane`: drop a k-row prefill (vector
        ``prompt_len`` — per-row fill levels, so every leaf already
        carries the batch axis) into lanes ``lane_idx``.  One jitted
        scatter per group instead of 3 eager ops per lane, compiled once
        per group size k."""
        return self._insert(lanes, jnp.asarray(lane_idx, jnp.int32), cache)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _insert(self, lanes, idx, cache):
        return jax.tree.map(lambda big, one: big.at[:, idx].set(one),
                            lanes, cache)

    # -------------------------------------------------------------- segments
    def _live(self, tok, produced, plen, max_new, eos, active):
        """Per-lane continuation mask; the same predicate order as the
        serial oracle (EOS, ring budget, request budget)."""
        return (active
                & (tok != eos)
                & (plen + produced < self.max_len)
                & (produced < max_new))

    def _segment_impl(self, params, caches, tok, produced, plen, max_new,
                      eos, active):
        """Run up to ``segment_len`` steps across all lanes.

        All per-lane carries are (C,) arrays: ``tok`` last emitted token,
        ``produced`` tokens emitted (incl. the prefill token), ``plen``
        prompt length, ``max_new`` request budget, ``active`` lane
        occupancy.  Returns (buf (C, K) int32 -1-padded, tok, produced,
        caches, stopped (C,) bool, dead () int32) — ``dead`` counts
        lane-steps burned on occupied-but-stopped lanes (the masked
        compute a stopped lane wastes until the segment's survivors
        finish; the PR-5 trade-off, reported as ``dead_steps``).
        """
        C, K = self.n_lanes, self.segment_len
        buf0 = jnp.full((C, K), -1, jnp.int32)

        def live(tok, produced):
            return self._live(tok, produced, plen, max_new, eos, active)

        def cond(c):
            i, tok, produced, _, _, _ = c
            return (i < K) & live(tok, produced).any()

        def body(c):
            i, tok, produced, caches, buf, dead = c
            lv = live(tok, produced)
            dead = dead + (active & ~lv).sum().astype(jnp.int32)
            # one natively batched step; stopped lanes compute dead values
            # that the lv masks below keep out of every visible carry
            logits, caches = self.lm.decode_step(
                params, caches, {"tokens": tok.reshape(C, 1)})
            new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok = jnp.where(lv, new_tok, tok)
            buf = jax.lax.dynamic_update_slice(
                buf, jnp.where(lv, tok, -1)[:, None], (0, i))
            return (i + 1, tok, produced + lv.astype(jnp.int32), caches,
                    buf, dead)

        _, tok, produced, caches, buf, dead = jax.lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), tok, produced, caches, buf0,
             jnp.zeros((), jnp.int32)))
        return buf, tok, produced, caches, ~live(tok, produced), dead

    def run_segment(self, params, caches, tok, produced, plen, max_new,
                    eos, active, produced_before):
        """One host-level segment call.

        The lane carries (``tok``/``produced``/``plen``/``max_new``/
        ``eos``/``active``) are device arrays — callers keep them
        resident across segments and re-upload only when admission
        changes the lane composition, so a steady-state segment costs one
        jit dispatch plus one host sync (the per-segment conversions were
        the dominant cost of the naive numpy round trip).
        ``produced_before`` is the host-side produced counts going in.

        Returns ``(new_tokens, tok, produced, caches, stopped,
        produced_np, dead_steps)``: ``tok``/``produced`` device arrays
        for the next segment, ``stopped``/``produced_np`` writable host
        copies, ``new_tokens[i]`` the tokens lane ``i`` emitted (in
        order), and ``dead_steps`` the lane-steps this segment burned on
        occupied-but-stopped lanes.
        """
        C = self.n_lanes
        buf, tok_j, produced_j, caches, stopped, dead = self._segment(
            params, caches, tok, produced, plen, max_new, eos, active)
        buf_np = np.asarray(buf)                  # one host sync per segment
        produced_np = np.array(produced_j)
        new_tokens = [
            [int(x) for x in buf_np[i, :max(0, int(produced_np[i])
                                            - int(produced_before[i]))]]
            for i in range(C)]
        return (new_tokens, tok_j, produced_j, caches, np.array(stopped),
                produced_np, int(dead))


class PagedLaneDecoder(LaneDecoder):
    """Lane decoder over a block-paged KV pool (serving/paging.py).

    Same segment loop and stop semantics as :class:`LaneDecoder`, but the
    caches are shared physical pools addressed through per-lane block
    tables (models/model.py ``init_paged_cache``): back-fill scatters a
    contiguous prefill cache into the lane's pages, prefix-hit admission
    gathers cached pages back into a contiguous buffer for an extend
    prefill, and page growth/release only rewrites block-table rows.
    Per-lane tokens stay bitwise-equal to the ring path — every logical
    slot holds the same value either way (tests/test_paging.py).
    """

    def __init__(self, lm, max_len: int, n_lanes: int, segment_len: int = 16,
                 *, n_pages: int, page_size: int):
        super().__init__(lm, max_len, n_lanes, segment_len)
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} not a multiple of "
                             f"page_size {page_size}")
        self.n_pages = int(n_pages)        # physical pool incl. trash page 0
        self.page_size = int(page_size)

    # ------------------------------------------------------------ lane admin
    def init_lanes(self):
        """Zero paged caches: pools of ``n_pages`` pages plus per-lane
        block tables (all slots 0 = the pinned trash page)."""
        return self.lm.init_paged_cache(self.n_lanes, self.max_len,
                                        self.n_pages, self.page_size)

    def insert_paged(self, lanes, lane_idx, pcache, bt_rows, tgt):
        """Scatter a k-row contiguous prefill cache into the pool.

        ``pcache`` leaves are (rep, k, Bf, KV, hd) contiguous buffers
        (``_run_prefill_group`` output or an extend prefill); ``bt_rows``
        (k, P) is each lane's full block table; ``tgt`` (k, ceil(Bf/ps))
        maps each Bf-chunk to the physical page that should receive it —
        0 (trash) for pad chunks beyond the prompt and for prefix-hit
        pages whose contents already live in the pool."""
        import jax.numpy as jnp
        return self._insert_paged(lanes, jnp.asarray(lane_idx, jnp.int32),
                                  pcache, jnp.asarray(bt_rows, jnp.int32),
                                  jnp.asarray(tgt, jnp.int32))

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _insert_paged(self, lanes, idx, pcache, bt_rows, tgt):
        ps = self.page_size
        out = []
        for big, one in zip(lanes, pcache):
            rep, k, Bf, KV, hd = one["k"].shape
            nchunk = -(-Bf // ps)
            pad = nchunk * ps - Bf
            ck, cv = one["k"], one["v"]
            if pad:
                widths = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
                ck, cv = jnp.pad(ck, widths), jnp.pad(cv, widths)
            ck = ck.reshape(rep, k * nchunk, ps, KV, hd)
            cv = cv.reshape(rep, k * nchunk, ps, KV, hd)
            tflat = tgt.reshape(-1)
            new = dict(big)
            # page-pool scatter; duplicate indices only ever hit the
            # trash page, where write order is irrelevant
            new["k"] = big["k"].at[:, tflat].set(ck)
            new["v"] = big["v"].at[:, tflat].set(cv)
            tval = one["t"]
            if tval.ndim == 1:             # scalar-fill prefill: (rep,)
                tval = tval[:, None]
            new["t"] = big["t"].at[:, idx].set(tval)
            new["bt"] = big["bt"].at[:, idx].set(bt_rows)
            out.append(new)
        return tuple(out)

    def gather_prefix(self, lanes, pages, prefix_len: int):
        """Materialize cached pages as a contiguous (B=1) prefill cache
        at fill level ``prefix_len`` — the input to an extend prefill.
        ``pages`` (nf,) physical page per logical block; slots past the
        matched prefix may be 0 (trash): the extend prefill overwrites
        them before anything attends there."""
        import jax.numpy as jnp
        return self._gather_prefix(lanes, jnp.asarray(pages, jnp.int32),
                                   jnp.asarray(prefix_len, jnp.int32))

    @functools.partial(jax.jit, static_argnums=0)
    def _gather_prefix(self, lanes, pages, fill):
        out = []
        for c in lanes:
            rep, _, ps, KV, hd = c["k"].shape
            nf = pages.shape[0]
            out.append({
                "k": c["k"][:, pages].reshape(rep, 1, nf * ps, KV, hd),
                "v": c["v"][:, pages].reshape(rep, 1, nf * ps, KV, hd),
                "t": jnp.full((rep,), fill, jnp.int32),
            })
        return tuple(out)

    def set_bt(self, lanes, lane_idx, bt_rows):
        """Rewrite block-table rows in place: page growth extends a busy
        lane's table; release zeroes it so the lane's dead writes land on
        the trash page instead of a reallocated page."""
        import jax.numpy as jnp
        return self._set_bt(lanes, jnp.asarray(lane_idx, jnp.int32),
                            jnp.asarray(bt_rows, jnp.int32))

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _set_bt(self, lanes, idx, rows):
        return tuple({**c, "bt": c["bt"].at[:, idx].set(rows)}
                     for c in lanes)


class _SpecLaneMixin:
    """Draft-verify speculation over a lane decoder's segment loop.

    Mixed into :class:`LaneDecoder` / :class:`PagedLaneDecoder`, this
    replaces the one-token-per-step segment body with *rounds* of
    :class:`SpeculativeDecoder` semantics, vectorized across lanes: every
    round runs the shared draft model ``draft_k`` chained steps for all
    lanes at once, verifies all lanes' chains with ONE multi-position
    target forward (``LM.verify_step`` — K+1 positions against the
    ring/paged KV in a single dispatch), and commits each lane's accepted
    prefix independently.  Per lane the emitted tokens are target
    argmaxes conditioned on accepted context only, so per-lane sequences
    stay bitwise-equal to the non-speculative reference regardless of
    per-lane acceptance (tests/test_speculative.py).

    The lane caches become a dict pytree ``{"tgt", "dr", "has_tail",
    "tail"}``: the target caches in their native layout (ring or paged),
    the draft caches always as a per-lane ring (draft KV is charged
    against the engine's memory budget / page pool by the admission
    layer, but physically lives in its own buffers — it is never
    content-addressed or shared), plus the per-lane catch-up state.  All
    admission-side operations (:meth:`insert_lanes`,
    :meth:`insert_paged`, :meth:`gather_prefix`, :meth:`set_bt`) route to
    the target half unchanged; :meth:`insert_draft` drops the draft
    prefill in and clears the lane's tail.

    Rollback is fill-level-only in both caches: a rejected draft leaves
    stale KV above the committed ``t`` that the verify mask never attends
    and that the next round's writes overwrite in order — no
    recompilation, no cleanup pass.  One caveat inherited from the ring
    layout: a draft chain launched within ``draft_k`` slots of
    ``max_len`` wraps/drops writes, which can only *lower* acceptance on
    the final tokens of a window-filling request, never change emitted
    tokens (the verify forward gates every emission).

    A segment runs ``rounds = max(1, segment_len // (draft_k+1))``
    rounds, so a segment still emits at most ~``segment_len`` tokens per
    lane and host sync frequency is unchanged.  ``run_segment`` keeps the
    base 7-tuple contract and additionally stashes per-lane
    ``last_drafted`` / ``last_accepted`` (host arrays) for the engine's
    acceptance-rate accounting.
    """

    def _init_spec(self, draft_lm, draft_params, draft_k: int):
        if draft_k < 1:
            raise ValueError("draft_k must be >= 1 (K=0 is the fused path)")
        if self.lm.cfg.vocab_size != draft_lm.cfg.vocab_size:
            raise ValueError(
                f"draft/target vocab mismatch: {draft_lm.cfg.vocab_size} "
                f"vs {self.lm.cfg.vocab_size}")
        self.draft_lm = draft_lm
        self.draft_params = draft_params
        self.draft_k = int(draft_k)
        self.rounds = max(1, self.segment_len // (self.draft_k + 1))
        self.last_drafted = np.zeros(self.n_lanes, np.int64)
        self.last_accepted = np.zeros(self.n_lanes, np.int64)
        self._spec_segment = jax.jit(self._spec_segment_impl,
                                     donate_argnums=(2,))

    # ------------------------------------------------------------ lane admin
    def init_lanes(self):
        dr = []
        for c in self.draft_lm.init_cache(self.n_lanes, self.max_len):
            if isinstance(c, dict) and "t" in c:
                c = dict(c)
                c["t"] = jnp.zeros(c["t"].shape + (self.n_lanes,),
                                   c["t"].dtype)
            dr.append(c)
        return {"tgt": super().init_lanes(), "dr": tuple(dr),
                "has_tail": jnp.zeros((self.n_lanes,), bool),
                "tail": jnp.zeros((self.n_lanes,), jnp.int32)}

    def insert_lane(self, lanes, lane, cache):
        return {**lanes,
                "tgt": super().insert_lane(lanes["tgt"], lane, cache)}

    def insert_lanes(self, lanes, lane_idx, cache):
        return {**lanes,
                "tgt": super().insert_lanes(lanes["tgt"], lane_idx, cache)}

    def insert_draft(self, lanes, lane_idx, cache):
        """Drop a k-row draft prefill into lanes ``lane_idx`` and clear
        their catch-up tails (a fresh request has no pending draft)."""
        idx = jnp.asarray(lane_idx, jnp.int32)
        return {**lanes, "dr": self._insert(lanes["dr"], idx, cache),
                "has_tail": lanes["has_tail"].at[idx].set(False)}

    def gather_prefix(self, lanes, pages, prefix_len: int):
        return super().gather_prefix(lanes["tgt"], pages, prefix_len)

    def insert_paged(self, lanes, lane_idx, pcache, bt_rows, tgt):
        return {**lanes, "tgt": super().insert_paged(
            lanes["tgt"], lane_idx, pcache, bt_rows, tgt)}

    def set_bt(self, lanes, lane_idx, bt_rows):
        return {**lanes,
                "tgt": super().set_bt(lanes["tgt"], lane_idx, bt_rows)}

    # -------------------------------------------------------------- segments
    def _spec_segment_impl(self, params, draft_params, caches, tok,
                           produced, plen, max_new, eos, active):
        """Run ``rounds`` draft-verify rounds across all lanes.

        Same carries as :meth:`LaneDecoder._segment_impl`; returns
        (buf (C, rounds*(K+1)) int32 -1-padded, tok, produced, caches,
        stopped, dead, drafted (C,), accepted (C,)) — ``dead`` counts
        verify positions burned on occupied-but-stopped lanes; wasted
        *draft* positions are ``drafted - accepted``, accounted by the
        engine so the split stays visible in stats.
        """
        C, K, R = self.n_lanes, self.draft_k, self.rounds
        W = K + 1
        BUF = R * W
        idx_w = jnp.arange(W, dtype=jnp.int32)
        buf0 = jnp.full((C, BUF), -1, jnp.int32)
        eos_c = eos[:, None] if jnp.ndim(eos) == 1 else eos

        def live(tok, produced):
            return self._live(tok, produced, plen, max_new, eos, active)

        def cond(c):
            r, tok, produced = c[0], c[1], c[2]
            return (r < R) & live(tok, produced).any()

        def body(c):
            (r, tok, produced, tgtc, drc, has_tail, tail, buf, wp, dead,
             drafted, accepted) = c
            lv = live(tok, produced)
            lvi = lv.astype(jnp.int32)
            dead = dead + W * (active & ~lv).sum().astype(jnp.int32)

            # --- draft: catch-up step + K chained steps ----------------
            # Catch-up consumes a full-accept round's unconsumed tail;
            # lanes without one feed their pending token as a dummy (the
            # fill reset below voids the slot advance, the duplicate
            # write is overwritten by the chain's first real write, and
            # step-0 logits are never used).
            dr_t0 = [dc["t"] for dc in drc]
            feed0 = jnp.where(has_tail, tail, tok)
            _, drc = self.draft_lm.decode_step(
                draft_params, drc, {"tokens": feed0.reshape(C, 1)})
            ht = has_tail.astype(jnp.int32)
            drc = tuple({**dc, "t": t0 + ht[None, :]}
                        for dc, t0 in zip(drc, dr_t0))

            def dstep(carry, _):
                cur, dc = carry
                lg, dc = self.draft_lm.decode_step(
                    draft_params, dc, {"tokens": cur.reshape(C, 1)})
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (nxt, dc), nxt

            (_, drc), d = jax.lax.scan(dstep, (tok, drc), None, length=K)
            d = d.T                                          # (C, K)

            # --- verify: one multi-position target forward -------------
            base_t = tgtc[0]["t"][0]                         # (C,) fills
            feed = jnp.concatenate([tok[:, None], d], axis=1)
            vlog, tgtc = self.lm.verify_step(params, tgtc,
                                             {"tokens": feed})
            a = jnp.argmax(vlog, axis=-1).astype(jnp.int32)  # (C, W)

            # --- acceptance: longest matching prefix, oracle stops -----
            ok = (d == a[:, :K]).astype(jnp.int32)
            m_chain = jnp.cumprod(ok, axis=1).sum(axis=1)
            cap = jnp.minimum(m_chain + 1,
                              jnp.minimum(self.max_len - plen - produced,
                                          max_new - produced))
            is_eos = (a == eos_c) & (idx_w[None, :] < cap[:, None])
            n_emit = jnp.where(is_eos.any(axis=1),
                               jnp.argmax(is_eos, axis=1)
                               .astype(jnp.int32) + 1, cap)
            n_emit = jnp.where(lv, n_emit, 0)

            # --- commit ------------------------------------------------
            valid = idx_w[None, :] < n_emit[:, None]
            slot = wp[:, None] + idx_w[None, :]
            hit = ((jnp.arange(BUF, dtype=jnp.int32)[None, None, :]
                    == slot[:, :, None]) & valid[:, :, None])
            buf = jnp.where(hit.any(axis=1),
                            (a[:, :, None] * hit).sum(axis=1), buf)
            wp = wp + n_emit
            last = jnp.take_along_axis(
                a, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            tok = jnp.where(lv, last, tok)
            produced = produced + n_emit
            tgtc = tuple({**tc, "t": tc["t"] + n_emit[None, :]}
                         for tc in tgtc)
            # draft keeps the accepted drafts only; stopped lanes restore
            # their pre-round fill (their chain steps were dead writes)
            n_keep = jnp.minimum(n_emit, K)
            drc = tuple(
                {**dc, "t": jnp.where(lv[None, :],
                                      (base_t + n_keep)[None, :], t0)}
                for dc, t0 in zip(drc, dr_t0))
            full = n_emit == W
            has_tail = jnp.where(lv, full, has_tail)
            tail = jnp.where(lv & full, d[:, K - 1], tail)
            drafted = drafted + K * lvi
            accepted = accepted + n_emit - lvi
            return (r + 1, tok, produced, tgtc, drc, has_tail, tail, buf,
                    wp, dead, drafted, accepted)

        z = jnp.zeros((C,), jnp.int32)
        (_, tok, produced, tgtc, drc, has_tail, tail, buf, _, dead,
         drafted, accepted) = jax.lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), tok, produced, caches["tgt"],
             caches["dr"], caches["has_tail"], caches["tail"], buf0, z,
             jnp.zeros((), jnp.int32), z, z))
        caches = {"tgt": tgtc, "dr": drc, "has_tail": has_tail,
                  "tail": tail}
        return (buf, tok, produced, caches, ~live(tok, produced), dead,
                drafted, accepted)

    def run_segment(self, params, caches, tok, produced, plen, max_new,
                    eos, active, produced_before):
        """Same contract as :meth:`LaneDecoder.run_segment`; additionally
        stashes per-lane ``last_drafted`` / ``last_accepted`` host arrays
        for the engine's acceptance accounting."""
        C = self.n_lanes
        (buf, tok_j, produced_j, caches, stopped, dead, drafted,
         accepted) = self._spec_segment(
            params, self.draft_params, caches, tok, produced, plen,
            max_new, eos, active)
        buf_np = np.asarray(buf)                  # one host sync per segment
        produced_np = np.array(produced_j)
        self.last_drafted = np.array(drafted)
        self.last_accepted = np.array(accepted)
        new_tokens = [
            [int(x) for x in buf_np[i, :max(0, int(produced_np[i])
                                            - int(produced_before[i]))]]
            for i in range(C)]
        return (new_tokens, tok_j, produced_j, caches, np.array(stopped),
                produced_np, int(dead))


class SpeculativeLaneDecoder(_SpecLaneMixin, LaneDecoder):
    """Ring-cache lane decoder with draft-verify speculation."""

    def __init__(self, lm, draft_lm, draft_params, max_len: int,
                 n_lanes: int, segment_len: int = 16, *, draft_k: int):
        LaneDecoder.__init__(self, lm, max_len, n_lanes, segment_len)
        self._init_spec(draft_lm, draft_params, draft_k)


class SpeculativePagedLaneDecoder(_SpecLaneMixin, PagedLaneDecoder):
    """Block-paged lane decoder with draft-verify speculation.  The
    target KV stays paged; the draft KV rides a per-lane ring whose
    footprint the paged admission layer charges as anonymous pages."""

    def __init__(self, lm, draft_lm, draft_params, max_len: int,
                 n_lanes: int, segment_len: int = 16, *, n_pages: int,
                 page_size: int, draft_k: int):
        PagedLaneDecoder.__init__(self, lm, max_len, n_lanes, segment_len,
                                  n_pages=n_pages, page_size=page_size)
        self._init_spec(draft_lm, draft_params, draft_k)


def geometric_buckets(max_len: int, floor: int = 16) -> tuple:
    """Prefill padding buckets: powers of two from ``floor`` up to and
    including ``max_len`` — a mixed-length admission stream compiles
    O(log(max_len)) prefill programs instead of one per distinct length."""
    buckets = []
    b = floor
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


def bucket_for(n: int, buckets) -> int:
    """Smallest bucket >= n; lengths beyond the last bucket prefill at
    exact length (the seed behavior — the decoder can't extend past
    ``max_len`` anyway, so rounding such a prompt up to a bigger pow2
    would only buy a compile of a cache shape that is never decoded)."""
    for b in buckets:
        if n <= b:
            return b
    return n
