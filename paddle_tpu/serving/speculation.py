"""Speculative decoding behind one object: draft, verify, accept.

A `SlotEngine` built with ``spec_len`` = k > 0 holds one `Speculation`;
a plain engine holds None and runs none of this. Each round, between
the engine's consume and its one dispatch, `propose` drafts up to k
tokens a decoding slot from a draft model (the target itself when none
is given) and stages ``[next, d_1..d_k]`` across the chunk columns the
slot already owns; the unified step projects those k+1 columns too
(`out["verify"]`), and after the engine's commit `commit` accepts or
resamples per slot (`_accept`: why greedy stays bitwise plain greedy,
and why a rejected suffix's KV is harmless).

The draft model runs its own compiled micro-step (`serving_draft`) over
separate pools sharing the ENGINE's block tables and allocator, so one
block id addresses both caches. Its cache trails the committed sequence
(per-slot `dfill`) and self-heals by catch-up, so a faulted draft phase
simply degrades the round to plain decode: every slot still commits
exactly its picked token, no losses, no duplicates.

Fault sites: ``serving.draft`` before each draft phase (raise = degrade
that round), ``serving.verify`` before each verify dispatch (raise =
step error, fails in-flight requests like ``serving.step``).
"""

from __future__ import annotations

import numpy as np

from .. import observe, profiler
from ..core.tensor import Tensor
from ..engine import functional_apply, state_values
from ..framework import faults

__all__ = ["Speculation", "speculative_accept"]


def speculative_accept(p_list, q_list, proposals, rng):
    """Leviathan-style rejection sampling over one drafted chain.

    `p_list[j]` / `q_list[j]` are the (identically warped) target and
    draft probability vectors at the position of `proposals[j]`. Accept
    d_j while ``u_j < min(1, p_j(d_j) / q_j(d_j))``; on first rejection
    resample from the residual ``normalize(max(p - q, 0))``. Returns
    ``(accepted_count, resampled_token_or_None)`` — None means every
    proposal survived (the caller then samples the bonus token from the
    verify step's final logits row, completing the k+1-per-round
    upside). The emitted-token distribution equals sampling from p
    directly — certified by the histogram test in
    tests/test_serving_spec.py. Pure host-side numpy so the invariant
    is testable without an engine."""
    for j, d in enumerate(proposals):
        p, q = p_list[j], q_list[j]
        if rng.random_sample() < min(1.0, float(p[d]) / max(float(q[d]),
                                                            1e-20)):
            continue
        residual = np.maximum(p - q, 0.0)
        tot = residual.sum()
        if tot <= 0.0:
            # p == q exactly and still rejected (u landed on the
            # boundary): any residual draw is p-distributed; use p
            residual, tot = p, p.sum()
        return j, int(rng.choice(residual.size, p=residual / tot))
    return len(proposals), None


class _SlotDraft:
    """One slot's draft-side state (`_Slot.spec`). The draft cache
    trails the committed sequence — positions [0, dfill) hold draft KV
    for tokens[0:dfill]; `fed` logs every token fed to it this round
    (committed catch-up AND proposals) so dfill advances exactly as far
    as the commit agreed with what was fed, whatever the round's
    outcome (accept, reject, degrade, mid-phase fault)."""

    __slots__ = ("dfill", "fed", "drafted", "qdists")

    def __init__(self):
        self.dfill = 0
        self.fed: list = []
        self.drafted: list = []   # this round's proposals d_1..d_s
        self.qdists: list = []    # warped draft dists per proposal


class Speculation:
    """The draft model, its weights, pools and compiled micro-step, and
    a round's three moves: `propose` (draft + stage), the engine's one
    verify dispatch in between, `commit` (accept / resample)."""

    def __init__(self, eng, draft_model, spec_len):
        import jax
        import jax.numpy as jnp

        from ..quantization import dequantize_state, is_quantized_state

        self.eng = eng
        self.spec_len = spec_len
        self.model = draft_model if draft_model is not None else eng.model
        self.model.eval()
        cfg, dcfg = eng.model.config, self.model.config
        if dcfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft vocab {dcfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}")
        if dcfg.max_seq_len < eng.max_seq_len:
            raise ValueError(
                f"draft max_seq_len {dcfg.max_seq_len} < engine "
                f"max_seq_len {eng.max_seq_len}")
        # draft weights stay float (the draft is the small model)
        self.values = dict(state_values(self.model)) \
            if draft_model is not None else dict(eng._values)
        if is_quantized_state(self.values):
            self.values = dict(dequantize_state(self.values))
        self.layout = self.model.cache_layout()
        # a rejected draft is rolled out of a KV pool by position (its
        # rows are masked, then overwritten); out of a recurrent state
        # it cannot be
        eng._refuse_state_arrays("speculation (spec_len > 0)")
        # and its rows do not move with a window group's table
        eng._refuse_block_groups("speculation (spec_len > 0)")
        if self.layout.state or len(self.layout.groups) > 1:
            raise ValueError(
                "speculation: the draft model's cache layout keeps "
                "per-slot state arrays or a windowed block group, which "
                "a rejected draft cannot be rolled out of")
        self.pools = eng._zero_pools(self.layout, place=False)
        self.pool_bytes = eng._pool_bytes(self.layout)
        # the draft trace is a separate, narrower program
        self.chunk = spec_len + 1
        # this round's live slots, (slot index, slot, draft budget),
        # from `propose` to `commit`
        self._round: list = []

        def serving_draft(dvalues, tok, pos, nvalid, tables, pools):
            eng._count_compile("draft")
            observe.record_compile(
                "serving.draft",
                signature=observe.signature_of(tok, pos, tables))

            def run(m):
                hv, new_pools, _aux = m.paged_forward(
                    tok, pos, nvalid, tables, pools)
                last = hv[jnp.arange(hv.shape[0]), nvalid - 1]
                return m.logits(Tensor(last[:, None, :])), new_pools

            logits, new_pools = functional_apply(self.model, dvalues, run)
            lv = jnp.asarray(logits)[:, 0, :].astype(jnp.float32)
            return lv, new_pools

        self._draft = jax.jit(serving_draft, donate_argnums=(5,))

    def warmup(self, pos, nvalid):
        """Trace the draft micro-step on null tables, like the step."""
        self._dispatch(np.zeros((self.eng.max_slots, self.chunk), np.int32),
                       pos, nvalid)

    def _dispatch(self, tok, pos, nvalid):
        """One call of the compiled draft micro-step; the draft pools
        are donated to it and rebound to its outputs (only the loop's
        thread ever reads them)."""
        import jax.numpy as jnp

        lv, self.pools = self._draft(
            self.values, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(nvalid), jnp.asarray(self.eng._bt), self.pools)
        return lv

    def _recover_pools(self):
        """The draft's twin of the engine's `_recover_pools`: a draft
        call that raised after its dispatch leaves empty draft pools,
        and every slot's draft cache starts over (`dfill` 0): the next
        round's catch-up rewrites it, as after any degraded round."""
        eng = self.eng
        if not eng._lost(eng._arrays(self.pools)):
            return
        self.pools = eng._zero_pools(self.layout, place=False)
        for slot in eng._slots:
            if slot is not None and slot.spec is not None:
                slot.spec.dfill, slot.spec.fed = 0, []

    # -- between consume and dispatch ---------------------------------------

    def propose(self, live, tok, nvalid):
        """Draft up to spec_len proposals a decoding slot (catch-up +
        propose over the shared block tables) and stage them behind the
        token the engine already put in column 0. A fault in the draft
        phase degrades the round to plain decode: proposals are dropped
        and the draft cache keeps whatever catch-up landed. Each slot's
        draft length is capped at its remaining token budget so every
        staged position stays inside its allocated blocks."""
        eng = self.eng
        self._round = []
        for i in live:
            slot = eng._slots[i]
            if slot.spec is None:
                slot.spec = _SlotDraft()
            # a prefilling slot joins the draft phase with no budget, so
            # the draft cache ingests its prompt alongside the target's
            budget = 0 if slot.state == "prefill" else min(
                self.spec_len,
                slot.req.gen.get("max_new_tokens", 16) - slot.produced)
            self._round.append((i, slot, budget))
        try:
            faults.fault_point("serving.draft")
            with observe.phase("draft", cat="serving"):
                self._run_draft(self._round)
        except Exception:  # noqa: BLE001 — degrade to plain decode
            eng.metrics.inc("spec_draft_faults")
            self._recover_pools()
            for _, slot, _ in self._round:
                slot.spec.drafted = []
        for i, slot, _ in self._round:
            props = slot.spec.drafted   # never more than the budget
            if props:
                tok[i, 1:1 + len(props)] = props
                nvalid[i] = 1 + len(props)
        faults.fault_point("serving.verify")

    def _run_draft(self, work):
        """Drive the ONE compiled draft micro-step until every working
        slot has caught its draft cache up to the committed sequence and
        sampled its proposals. Each iteration batches one [max_slots,
        spec_len+1] call: catch-up slots feed their next committed
        segment, proposing slots feed their latest proposal; idle rows
        route beyond the table so their writes land in the null block.
        Successful feeds are logged to `fed` AFTER the call returns, so
        a mid-phase fault leaves bookkeeping consistent with what
        actually landed in the draft pools."""
        eng = self.eng
        width = self.chunk
        idle_pos = eng.blocks_per_slot * eng.block_size
        qlast: dict = {}
        limit = -(-eng.max_seq_len // width) + self.spec_len + 4
        for _ in range(limit):
            dtok = np.zeros((eng.max_slots, width), np.int32)
            dpos = np.full((eng.max_slots,), idle_pos, np.int32)
            dnval = np.ones((eng.max_slots,), np.int32)
            feeds: dict = {}
            for i, slot, s_i in work:
                st = slot.spec
                base = st.dfill + len(st.fed)
                target = slot.tokens
                if base < len(target):
                    n = min(width, len(target) - base)
                    seg = target[base:base + n]
                    dtok[i, :n] = seg
                    dpos[i] = base
                    dnval[i] = n
                    feeds[i] = (st, seg)
                elif s_i and len(st.drafted) < s_i:
                    d = self._pick(slot, qlast[i])
                    st.drafted.append(d)
                    # the FINAL proposal is never fed back: no later
                    # proposal conditions on it, verify recomputes p
                    if len(st.drafted) < s_i:
                        dtok[i, 0] = d
                        dpos[i] = base
                        dnval[i] = 1
                        feeds[i] = (st, [d])
            if not feeds:
                return
            with profiler.RecordEvent("serving.draft", cat="serving"):
                lv = self._dispatch(dtok, dpos, dnval)
            lv = np.asarray(lv)
            for i, (st, seg) in feeds.items():
                st.fed.extend(int(t) for t in seg)
                qlast[i] = lv[i]
        raise RuntimeError(
            f"draft catch-up did not converge in {limit} micro-steps")

    def _pick(self, slot, qrow):
        """Sample one proposal from the draft distribution, recording
        the warped probs (sampling requests) for accept/reject."""
        gen = slot.req.gen
        if not gen.get("do_sample"):
            slot.spec.qdists.append(None)
            return int(qrow.argmax())
        p = self.eng._warp_probs(qrow, gen)
        slot.spec.qdists.append(p)
        return int(slot.rng.choice(p.size, p=p))

    # -- after the engine's commit ------------------------------------------

    def commit(self, verify, now):
        """Accept / resample over the verify rows, and move every live
        slot's draft-cache mark."""
        for i, slot, _ in self._round:
            self._accept(i, slot, verify[i], now)
        if any(budget for _, _, budget in self._round):
            self.eng.metrics.inc("spec_rounds")

    @staticmethod
    def _advance_dfill(st, seq):
        """Advance the draft-cache coverage mark exactly as far as this
        round's feeds agree with the (post-commit) token sequence:
        committed catch-up and ACCEPTED proposals advance it, a
        rejected suffix or degraded round stops it — the next round's
        catch-up rewrites from there. Clears the round scratch."""
        base, fed = st.dfill, st.fed
        j = 0
        while j < len(fed) and base + j < len(seq) \
                and fed[j] == seq[base + j]:
            j += 1
        st.dfill = base + j
        st.fed = []
        st.drafted = []
        st.qdists = []

    def _accept(self, i, slot, sv_i, now):
        """Host-side accept for one slot after a verify step; the
        engine's commit already moved it past its picked token and
        handed it the last column's pick and logits. Every token
        committed here is stamped `now`, the read-back's end.
        Greedy: accept the longest prefix of proposals that match the
        verify argmaxes, then hand the first-mismatch logits row (a
        host row of `verify`, with no pick behind it: `next_token`
        None) to the NEXT round's `_pick` — every emitted token is an
        argmax of the same logits the plain engine would compute,
        hence bitwise parity. Sampling: Leviathan accept /
        residual-resample through the identical `_warp_probs`
        transform (`speculative_accept`); a resampled token is
        committed with no logits behind it (`next_logits` None), which
        the engine's consume feeds instead of picking.
        All staged positions were already scattered into the paged pool
        in bulk by the verify step; the engine's `_pos` advances only
        over the committed prefix, and the garbage KV above it is
        overwritten by the next round's staging before any row can
        attend it."""
        eng, st = self.eng, slot.spec
        props = st.drafted
        s = len(props)
        if s == 0:
            # nothing staged (a prefilling slot, a degraded round): the
            # engine's own commit was all of it
            self._advance_dfill(st, slot.tokens)
            return
        gen = slot.req.gen
        eos = gen.get("eos_token_id")
        max_new = gen.get("max_new_tokens", 16)
        if not gen.get("do_sample"):
            a = 0
            while a < s and int(sv_i[a].argmax()) == props[a]:
                a += 1
            resampled = None
            # rejection: sv_i[a] is p(. | accepted prefix) — the next
            # _pick's argmax IS the rejection token; all-accept: the
            # bonus row
            nl = sv_i[a] if a < s else sv_i[s]
        else:
            p_list = [eng._warp_probs(sv_i[j], gen) for j in range(s)]
            a, resampled = speculative_accept(p_list, st.qdists[:s],
                                              props, slot.rng)
            nl = None if resampled is not None else sv_i[s]
        eng.metrics.observe_spec(i, s, a)
        finished = False
        m = 0
        for t in props[:a]:
            slot.tokens.append(int(t))
            slot.produced += 1
            slot.req.token_times.append(now)
            eng.metrics.inc("tokens_out")
            m += 1
            if (eos is not None and t == eos) or \
                    slot.produced >= max_new:
                finished = True
                break
        eng._pos[i] += m
        self._advance_dfill(st, slot.tokens)
        if finished:
            eng._evict(i)
            return
        if resampled is not None:
            slot.tokens.append(int(resampled))
            slot.produced += 1
            slot.req.token_times.append(now)
            eng.metrics.inc("tokens_out")
            slot.next_logits = slot.next_token = None
            if (eos is not None and resampled == eos) or \
                    slot.produced >= max_new:
                eng._evict(i)
            return
        slot.next_logits, slot.next_token = nl, None
