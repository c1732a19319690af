"""Continuous-batching rollout engine: slot-refill generation for the
GENERATE stage, with a multi-turn episode loop for agentic environments.

The lockstep path (:func:`repro.rl.rollout.generate`) pads every prompt to a
common length and scans all ``max_new`` decode steps even after every
sequence has emitted EOS — so real token throughput collapses as
response-length variance grows, exactly the failure mode AsyncFlow / LlamaRL
attribute their largest wins to fixing with in-flight batching. This module
is that fix on the DistFlow GENERATE stage:

  * a fixed pool of ``num_slots`` decode slots shares ONE persistent KV-cache
    arena (``model.init_caches(num_slots, smax)``); slot *i* is batch row *i*
    of every cache leaf, and each slot carries its own ``cache_len`` (the
    decode kernels already take per-sequence valid lengths);
  * when a slot's sequence hits EOS (or its token budget) the slot is freed
    and immediately refilled with the next prompt from the
    :class:`PromptQueue` — a fresh prefill is scattered over the slot's cache
    rows (``lm.scatter_cache_rows``, the slot-reset path) while the other
    slots' in-flight state is untouched;
  * refills are length-bucketed (prompts grouped by true length rounded up
    to ``prefill_bucket``) so a refill batch prefills at its bucket length
    instead of the global padded max, and optionally chunked
    (``lm.prefill_chunk``) so one long prefill is split into bounded pieces;
  * the decode loop is a ``lax.while_loop`` that early-exits on ``all(done)``
    once the prompt queue drains — the engine never pays lockstep's
    "scan to max_new regardless" tax.

Multi-turn episodes (``env=`` an :class:`repro.rl.envs.EnvRuntime`): a slot
whose sequence finishes a *turn* hands its response to the environment; if
the episode continues, it **re-enters the PromptQueue** as a continuation
item carrying its saved KV rows (``lm.gather_cache_rows``) and the feed
tokens ``[last response token] + observation``. When the continuation is
scheduled, the rows are scattered back over a free slot's arena rows
(``lm.scatter_cache_rows``) and ONLY the feed tokens are run through the
decode path — the shared prompt/response prefix is never re-prefilled, so
``last_stats["prefill_tokens_turn2plus"]`` counts observation tokens (plus
one carried response token per turn), not prefixes. Observation tokens are
excluded from ``response_mask`` and tagged 2 in the emitted ``role_mask``,
so losses/advantages never train on env tokens (docs/environments.md).

Determinism / equivalence contract: under a *fixed slot schedule* — one
length bucket, ``num_slots >= batch`` (every prompt prefilled at once, no
mid-stream refill) — the engine consumes the exact key schedule of lockstep
``generate`` (``k0`` for the prefill sample, ``split(k2, max_new-1)`` for
decode steps) and computes the same prefill/decode math on the same shapes,
so it is token-for-token identical to lockstep (asserted by
``tests/test_rollout_engine.py``). Decode steps past ``max_new - 1`` (which
only exist once refill has happened) derive keys by ``fold_in(k2, t)``.
Single-turn runs — env off, or a single-turn env, which only scores — take
this exact path (asserted by ``tests/test_envs.py``).

Metrics (``engine.last_stats``, surfaced by the GENERATE stage as
``rollout/*``): tokens/sec, padding-waste %, slot occupancy, decode steps,
refill counts, per-turn prefill token accounting. ``docs/rollout_engine.md``
has the slot lifecycle diagram and the metrics glossary.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.obs.trace import get_tracer
from repro.rl.rollout import RolloutResult, sample_token


def _true_lengths(prompts: np.ndarray, pad_id: int) -> np.ndarray:
    """Per-row count of tokens up to and including the last non-pad token
    (right-padded prompts; a fully-pad row counts 1 so it still prefills)."""
    nonpad = prompts != pad_id
    rev = nonpad[:, ::-1]
    last = prompts.shape[1] - np.argmax(rev, axis=1)  # index after last non-pad
    return np.where(nonpad.any(axis=1), last, 1).astype(np.int64)


class _Continuation:
    """A continuing episode waiting for a slot: the dataset row, the feed
    tokens (last response token + clipped observation), the saved KV rows,
    and the cache offset the feed starts at."""

    __slots__ = ("row", "feed", "cache_rows", "cache_len")

    def __init__(self, row: int, feed: np.ndarray, cache_rows, cache_len: int):
        self.row = row
        self.feed = np.asarray(feed, np.int32)
        self.cache_rows = cache_rows
        self.cache_len = int(cache_len)


class PromptQueue:
    """Length-bucketed FIFO over one iteration's pending work.

    Fresh prompts: each prompt's true (non-pad) length is rounded up to a
    multiple of ``bucket`` (0 = a single bucket at the batch's padded
    length — the lockstep-equivalent schedule); refills pop from one bucket
    at a time so every prefill batch shares a padded length. Within a
    bucket, dataset order is preserved.

    Continuations (:meth:`push`): continuing episodes re-enter the queue in
    exact-feed-length buckets (a continuation batch must share its feed
    width; feeds are short — an observation plus one carried token — so the
    bucket count stays small). ``pop_work`` prefers continuations —
    finishing in-flight episodes bounds the number of saved KV-row sets
    held off-arena — but only for ``STARVATION_LIMIT`` consecutive pops
    while fresh prompts wait, so sustained continuation pressure (an env
    that re-queues a continuation per finished turn, i.e. exactly as fast
    as slots free) cannot defer fresh prompts indefinitely.

    Both lanes pick the *fullest* bucket (maximal batch of one shape), which
    on its own would let a small bucket's head wait out every larger bucket;
    a pass counter ages each non-empty bucket that loses the selection and
    force-serves any bucket passed over ``STARVATION_LIMIT`` times. Every
    pending item is therefore served within a bounded number of pops, while
    schedules too short to trip the limits are untouched.
    """

    STARVATION_LIMIT = 4  # max times a non-empty lane/bucket is passed over

    def __init__(self, prompts: np.ndarray, *, pad_id: int, bucket: int = 0,
                 order=None):
        self.prompts = prompts
        B, Lp = prompts.shape
        self.true_len = _true_lengths(prompts, pad_id)
        if bucket <= 0:
            blens = np.full(B, Lp, np.int64)
        else:
            blens = np.minimum(-(-self.true_len // bucket) * bucket, Lp)
        self.bucket_len = blens
        self._buckets: Dict[int, deque] = {}
        self._cont: Dict[int, deque] = {}
        self._passes: Dict[int, int] = {}  # fresh-bucket aging
        self._cont_passes: Dict[int, int] = {}  # cont-bucket aging
        self._cont_streak = 0  # cont pops in a row while fresh waited
        for i in (range(B) if order is None else order):
            self._buckets.setdefault(int(blens[i]), deque()).append(i)

    def __len__(self) -> int:
        return (sum(len(q) for q in self._buckets.values())
                + sum(len(q) for q in self._cont.values()))

    def push(self, cont: _Continuation) -> None:
        """Re-enqueue a continuing episode (multi-turn env path)."""
        self._cont.setdefault(len(cont.feed), deque()).append(cont)

    @staticmethod
    def _select(buckets: Dict[int, deque], passes: Dict[int, int],
                limit: int) -> int:
        """Fullest bucket, unless one has been passed over ``limit`` times
        (then the oldest-starved, shortest-length one). Losing non-empty
        buckets age by one pass; the winner's counter resets."""
        aged = [b for b in buckets if passes.get(b, 0) >= limit]
        if aged:
            sel = min(aged, key=lambda b: (-passes[b], b))
        else:
            sel = max(buckets, key=lambda b: (len(buckets[b]), -b))
        for b in buckets:
            if b != sel:
                passes[b] = passes.get(b, 0) + 1
        passes.pop(sel, None)
        return sel

    def pop(self, n: int) -> Tuple[int, List[int]]:
        """Pop up to ``n`` fresh-prompt indices from the fullest bucket
        (ties break toward the shorter bucket length), except that a bucket
        passed over ``STARVATION_LIMIT`` times is served first. Returns
        (bucket_len, indices); FIFO within the bucket."""
        lb = self._select(self._buckets, self._passes, self.STARVATION_LIMIT)
        q = self._buckets[lb]
        take = [q.popleft() for _ in range(min(n, len(q)))]
        if not q:
            del self._buckets[lb]
        return lb, take

    def pop_work(self, n: int):
        """Pop up to ``n`` homogeneous work items: ``("cont", feed_len,
        [_Continuation, ...])`` or ``("prefill", bucket_len, [row, ...])``.
        Continuations go first — bounding off-arena KV — until they have
        monopolized ``STARVATION_LIMIT`` consecutive pops with fresh
        prompts waiting; then one fresh bucket is served. With no
        continuations this is exactly :meth:`pop` — the single-turn refill
        schedule is untouched."""
        serve_cont = self._cont and (
            not self._buckets or self._cont_streak < self.STARVATION_LIMIT)
        if serve_cont:
            self._cont_streak = self._cont_streak + 1 if self._buckets else 0
            K = self._select(self._cont, self._cont_passes,
                             self.STARVATION_LIMIT)
            q = self._cont[K]
            take = [q.popleft() for _ in range(min(n, len(q)))]
            if not q:
                del self._cont[K]
            return "cont", K, take
        self._cont_streak = 0
        lb, idxs = self.pop(n)
        return "prefill", lb, idxs


class _Episode:
    """Host-side record of one multi-turn episode (dataset row)."""

    __slots__ = ("env", "toks", "roles", "lps", "reward", "turn", "infos")

    def __init__(self, env):
        self.env = env
        self.toks: List[int] = []   # tokens after the prompt region
        self.roles: List[int] = []  # 1 = action, 2 = observation
        self.lps: List[float] = []  # behaviour logprobs (0 on observations)
        self.reward = 0.0
        self.turn = 0
        self.infos: List[dict] = []

    def record_turn(self, resp: np.ndarray, lps: np.ndarray) -> None:
        self.toks.extend(int(t) for t in resp)
        self.roles.extend([1] * len(resp))
        self.lps.extend(float(v) for v in lps)

    def record_obs(self, obs: np.ndarray) -> None:
        self.toks.extend(int(t) for t in obs)
        self.roles.extend([2] * len(obs))
        self.lps.extend([0.0] * len(obs))


class ContinuousRolloutEngine:
    """Slot-based continuous-batching generation engine.

    Drop-in for the jitted lockstep engine at the GENERATE stage: callable as
    ``engine(params, prompts, key) -> RolloutResult`` with identical output
    contract (tokens / response_mask / old_logprob / lengths in dataset
    order). Host code orchestrates slot bookkeeping; the three hot paths —
    the per-bucket refill prefill, the continuation feed, and the
    early-exiting decode burst — are jitted once per shape and reused across
    iterations.

    ``env`` (an :class:`repro.rl.envs.EnvRuntime`) switches the slot loop to
    the episode loop: one environment per sequence, up to ``max_turns``
    turns, observations appended via KV-preserving continuations. With
    ``env=None`` (default) the engine is the PR-4 single-turn engine,
    token-for-token.
    """

    def __init__(
        self,
        model: Model,
        *,
        max_new: int,
        temperature: float = 1.0,
        top_p: float = 1.0,
        eos_id: Optional[int] = None,
        pad_id: int = 0,
        num_slots: int = 0,
        prefill_chunk: int = 0,
        prefill_bucket: int = 0,
        refill_threshold: int = 1,
        env=None,
        max_turns: int = 1,
        turn_budget: int = 0,
        obs_budget: int = 16,
    ):
        if model.is_encdec or model.cfg.num_prefix_embeds:
            raise ValueError(
                "the continuous engine supports text decoder-only archs; "
                "use engine='lockstep' for enc-dec / prefix-modality models"
            )
        self.model = model
        self.max_new = max_new
        self.temperature = temperature
        self.top_p = top_p
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.num_slots = num_slots
        self.prefill_bucket = prefill_bucket
        # minimum count of newly-freed slots before a burst hands control
        # back for refill (while prompts pend). 1 = refill eagerly (maximum
        # occupancy); higher values trade a little slot idleness for fewer
        # host round-trips — useful when dispatch overhead is comparable to
        # a decode step, as on CPU hosts
        self.refill_threshold = max(1, refill_threshold)
        # multi-turn episode loop (None = single-turn slot loop)
        self.env = env
        self.max_turns = max(1, max_turns)
        if env is not None and self.max_turns > 1 and any(
                k[0] == "ssm" for k in model.cfg.layer_kinds()):
            # a done slot keeps executing decode steps (fed PAD) until the
            # burst exits; attention tolerates that — the garbage KV sits
            # past the valid cache_len and is sequentially overwritten
            # before it can be attended — but SSM recurrent state absorbs
            # every update irreversibly, so the rows saved at turn end
            # would resume the next turn from a corrupted state
            raise ValueError(
                "multi-turn environments support attention-only archs; "
                f"{model.cfg.name!r} has SSM mixer layers whose recurrent "
                "state cannot be preserved across turns (use max_turns=1 "
                "or an attention arch)"
            )
        # per-turn response cap (0 = max_new); observation clip per turn
        self.turn_budget = min(turn_budget, max_new) if turn_budget else max_new
        self.obs_budget = max(1, obs_budget)
        # chunked prefill is attention-only (SSM state doesn't carry between
        # chunks), needs an unwrapped cache (no SWA ring), and excludes
        # int8 caches: a chunk would attend the quantize->dequantized K/V
        # of its own prefix, diverging from whole-prompt prefill by far
        # more than float reassociation (~3e-2 in behaviour logprobs)
        kinds = model.cfg.layer_kinds()
        self._can_chunk = (
            prefill_chunk > 0
            and all(k[0] == "attn" for k in kinds)
            and model.cfg.sliding_window is None
            and not model.cfg.kv_quant
        )
        self.prefill_chunk = prefill_chunk if self._can_chunk else 0
        self.last_stats: Dict[str, float] = {}
        # per-episode env outputs of the last call (None when env is off):
        # {"rewards": (B,), "turns": (B,), "tool_calls": int}
        self.last_env: Optional[Dict[str, np.ndarray]] = None
        self._refill_jit: Dict[Tuple[int, int, int], callable] = {}
        self._burst_jit: Dict[Tuple[int, int], callable] = {}
        self._cont_jit: Dict[Tuple[int, int, int], callable] = {}

    # ------------------------------------------------------------------ #
    # jitted halves
    # ------------------------------------------------------------------ #
    def _seed_slots(self, R, logits, key, slots, lane_budget, new_len,
                    cur_tok, cache_len, resp_len, done, budget,
                    out_tok, out_lp):
        """Shared epilogue of the refill and continuation closures (traced
        inside their jits): sample each lane's first response token from
        ``logits``, reset the per-slot output rows, and scatter the lane
        state into the slot arrays (out-of-range slot ids = padding lanes,
        dropped). ``new_len`` is the lanes' cache length after the fill — a
        scalar bucket width for refills, a per-lane vector for
        continuations."""
        eos, pad, max_new = self.eos_id, self.pad_id, self.max_new
        tok0 = sample_token(logits, key, self.temperature, self.top_p)
        lane = jnp.arange(R)
        lp0 = jax.nn.log_softmax(logits, axis=-1)[lane, tok0]
        done0 = (tok0 == eos) if eos is not None else jnp.zeros((R,), bool)
        row_tok = jnp.full((R, max_new), pad, out_tok.dtype).at[:, 0].set(tok0)
        row_lp = jnp.zeros((R, max_new), out_lp.dtype).at[:, 0].set(lp0)
        cur_tok = cur_tok.at[slots].set(tok0, mode="drop")
        cache_len = cache_len.at[slots].set(new_len, mode="drop")
        resp_len = resp_len.at[slots].set(1, mode="drop")
        done = done.at[slots].set(done0 | (lane_budget <= 1), mode="drop")
        budget = budget.at[slots].set(lane_budget, mode="drop")
        out_tok = out_tok.at[slots].set(row_tok, mode="drop")
        out_lp = out_lp.at[slots].set(row_lp, mode="drop")
        return cur_tok, cache_len, resp_len, done, budget, out_tok, out_lp

    def _make_refill(self, R: int, Lb: int, smax: int):
        """Refill ``R`` lanes with a (padded) prompt batch of width ``Lb``:
        prefill, scatter the fresh cache rows over the arena at ``slots``
        (out-of-range ids = padding lanes, dropped), sample each lane's first
        response token, and reset the per-slot output rows. ``R`` is the
        refill batch width — the caller rounds the actual refill count up to
        a power of two so late-stream single-slot refills don't pay a
        full-pool prefill (and the compile count stays log-bounded)."""
        model = self.model
        chunk = self.prefill_chunk

        def refill(params, caches, prompts, slots, lane_budget, key,
                   cur_tok, cache_len, resp_len, done, budget,
                   out_tok, out_lp):
            if chunk > 0:
                rows = model.init_caches(R, smax)
                logits = None
                for off in range(0, Lb, chunk):
                    logits, rows = model.prefill_chunk(
                        params, prompts[:, off:off + chunk], rows, offset=off
                    )
            else:
                logits, rows, _ = model.prefill(params, prompts, smax=smax)
            caches = model.scatter_cache_rows(caches, rows, slots)
            (cur_tok, cache_len, resp_len, done, budget, out_tok,
             out_lp) = self._seed_slots(
                R, logits, key, slots, lane_budget, Lb,
                cur_tok, cache_len, resp_len, done, budget, out_tok, out_lp)
            return (caches, cur_tok, cache_len, resp_len, done, budget,
                    out_tok, out_lp)

        return jax.jit(refill, donate_argnames=("caches", "out_tok", "out_lp"))

    def _make_continue(self, R: int, K: int, smax: int):
        """Resume ``R`` continuing episodes on free slots: scatter each
        episode's saved KV rows over the arena at ``slots``, teacher-force
        the ``K`` feed tokens (last response token + observation) through the
        decode path — per-row cache offsets differ, which
        ``model.decode_step`` already supports — and sample each lane's
        first next-turn token from the final feed position's logits. Only
        the feed is processed: the shared prompt/response prefix is reused
        from the saved rows, never re-prefilled."""
        model = self.model
        V = model.cfg.padded_vocab

        def cont(params, caches, rows, slots, feed, start_len, lane_budget,
                 key, cur_tok, cache_len, resp_len, done, budget,
                 out_tok, out_lp):
            def body(carry, tok):
                rows, clen, _ = carry
                logits, rows, clen = model.decode_step(params, tok, rows, clen)
                return (rows, clen, logits), None

            init = (rows, start_len, jnp.zeros((R, V), jnp.float32))
            (rows, clen, logits), _ = jax.lax.scan(
                body, init, jnp.moveaxis(feed, 1, 0))
            caches = model.scatter_cache_rows(caches, rows, slots)
            (cur_tok, cache_len, resp_len, done, budget, out_tok,
             out_lp) = self._seed_slots(
                R, logits, key, slots, lane_budget, clen,
                cur_tok, cache_len, resp_len, done, budget, out_tok, out_lp)
            return (caches, cur_tok, cache_len, resp_len, done, budget,
                    out_tok, out_lp)

        return jax.jit(cont, donate_argnames=("caches", "out_tok", "out_lp"))

    def _make_burst(self, S: int):
        """The decode loop: a ``lax.while_loop`` stepping every slot, exiting
        as soon as (a) every slot is done — the early-exit on a drained
        queue — or (b) any slot *newly* finishes while prompts are pending,
        handing control back to the host for an immediate refill. The KV
        arena stays in one buffer for the whole burst: each step writes one
        row per slot and layer (``model.decode_step_sample``)."""
        model, temp, top_p = self.model, self.temperature, self.top_p
        eos, pad, max_new = self.eos_id, self.pad_id, self.max_new
        T = max_new - 1  # lockstep's decode-step count (key schedule length)
        threshold = self.refill_threshold

        def burst(params, caches, cur_tok, cache_len, resp_len, done, budget,
                  out_tok, out_lp, t, occ, step_keys, k2, has_pending):
            n_done_entry = jnp.sum(done)
            lane = jnp.arange(S)

            def cond(st):
                done = st[4]
                any_active = ~jnp.all(done)
                below_threshold = (jnp.sum(done) - n_done_entry) < threshold
                return any_active & (below_threshold | ~has_pending)

            def body(st):
                (caches, cur_tok, cache_len, resp_len, done, budget,
                 out_tok, out_lp, t, occ) = st
                occ = occ + jnp.sum(~done)
                # lockstep's exact key schedule for the first T steps
                # (jax.random.split is NOT prefix-stable, so the array is
                # sized exactly T); steps beyond T — which only exist after
                # a refill — fold the step index into k2
                kt = jax.lax.select(
                    t < T,
                    step_keys[jnp.minimum(t, T - 1)],
                    jax.random.fold_in(k2, t),
                )
                # fused decode+sample: logits never materialize outside the
                # kernel dispatch (ref mode is bitwise the old sequence)
                nxt, lp, caches, cache_len = model.decode_step_sample(
                    params, cur_tok, caches, cache_len, kt, temp, top_p=top_p
                )
                nxt = jnp.where(done, pad, nxt)
                lp = jnp.where(done, 0.0, lp)
                wr = (~done) & (resp_len < max_new)
                idx = jnp.where(wr, resp_len, max_new)  # OOB -> dropped
                out_tok = out_tok.at[lane, idx].set(nxt, mode="drop")
                out_lp = out_lp.at[lane, idx].set(lp, mode="drop")
                resp_len = resp_len + wr
                new_done = done
                if eos is not None:
                    new_done = new_done | (nxt == eos)
                new_done = new_done | (resp_len >= budget)
                return (caches, nxt, cache_len,
                        resp_len, new_done, budget, out_tok, out_lp,
                        t + 1, occ)

            st = (caches, cur_tok, cache_len, resp_len, done, budget,
                  out_tok, out_lp, t, occ)
            return jax.lax.while_loop(cond, body, st)

        # the arena and the output rows are updated in place: donated, a
        # call neither copies nor duplicates them
        return jax.jit(burst, donate_argnames=("caches", "out_tok", "out_lp"))

    # ------------------------------------------------------------------ #
    @staticmethod
    def _stack_cont_rows(items: List[_Continuation], R: int):
        """Stack the saved per-episode cache rows (leaves (N, 1, ...)) into
        an (N, R, ...) tree, zero-padding the unused lanes."""
        stacked = jax.tree.map(
            lambda *leaves: jnp.concatenate(leaves, axis=1),
            *[c.cache_rows for c in items])
        pad_n = R - len(items)
        if pad_n:
            stacked = jax.tree.map(
                lambda a: jnp.pad(
                    a, [(0, 0), (0, pad_n)] + [(0, 0)] * (a.ndim - 2)),
                stacked)
        return stacked

    # ------------------------------------------------------------------ #
    def __call__(self, params, prompts, key,
                 budgets: Optional[np.ndarray] = None) -> RolloutResult:
        """``budgets`` (B,) caps each sequence's response length at
        ``min(budgets[b], max_new)`` — same semantics as lockstep
        ``generate(budgets=...)``, but here a capped sequence *frees its
        slot* instead of padding out the scan. Under an env, the cap applies
        per turn (jointly with ``turn_budget``)."""
        with get_tracer().span("rollout/generate", cat="rollout",
                               seqs=prompts.shape[0]):
            return self._generate(params, prompts, key, budgets)

    def _generate(self, params, prompts, key, budgets) -> RolloutResult:
        t_start = time.perf_counter()
        prompts_np = np.asarray(jax.device_get(prompts), np.int32)
        B, Lp = prompts_np.shape
        max_new = self.max_new
        env_on = self.env is not None
        max_turns = self.max_turns if env_on else 1
        turn_cap = min(self.turn_budget, max_new) if env_on else max_new
        if budgets is None:
            budgets_np = np.full(B, turn_cap, np.int32)
        else:
            budgets_np = np.clip(
                np.asarray(jax.device_get(budgets), np.int32), 1, turn_cap)
        S = self.num_slots if self.num_slots > 0 else B
        S = max(1, min(S, B))
        # the arena must hold the longest possible episode: prompt + every
        # turn's response + every inter-turn feed (observation + 1 carried
        # response token)
        smax = Lp + max_turns * max_new + (max_turns - 1) * (self.obs_budget + 1)

        # episode setup: one env per dataset row; reset() supplies the
        # turn-1 context (built-ins return the prompt unchanged, so the
        # single-turn schedule — and its tokens — are untouched)
        episodes: List[Optional[_Episode]] = [None] * B
        if env_on:
            true0 = _true_lengths(prompts_np, self.pad_id)
            first_rows = np.full((B, Lp), self.pad_id, np.int32)
            for b in range(B):
                ep = _Episode(self.env.make_episode())
                obs0 = np.asarray(
                    ep.env.reset(prompts_np[b, : true0[b]]), np.int32).ravel()
                if len(obs0) > Lp:
                    raise ValueError(
                        f"env reset() returned {len(obs0)} tokens > prompt "
                        f"width {Lp}")
                first_rows[b, : len(obs0)] = obs0
                episodes[b] = ep
            queue_rows = first_rows
        else:
            queue_rows = prompts_np

        # known budgets + a real queue (S < B) -> longest-first (LPT) slot
        # packing: long sequences start first instead of draining alone at
        # the tail (the same policy as the coordinator's length-aware
        # balancing). With S == B there is no queue, and dataset order is
        # kept — that's the lockstep-equivalent fixed schedule.
        order = (np.argsort(-budgets_np, kind="stable")
                 if budgets is not None and S < B else None)
        queue = PromptQueue(queue_rows, pad_id=self.pad_id,
                            bucket=self.prefill_bucket, order=order)
        prefill_true_tokens = int(queue.true_len.sum())

        k0, k2 = jax.random.split(key)
        T = max_new - 1
        step_keys = (jax.random.split(k2, T) if T > 0
                     else jnp.zeros((1, 2), jnp.uint32))

        # slot state (device) -------------------------------------------- #
        caches = self.model.init_caches(S, smax)
        cur_tok = jnp.zeros((S,), jnp.int32)
        cache_len = jnp.zeros((S,), jnp.int32)
        resp_len = jnp.zeros((S,), jnp.int32)
        done = jnp.ones((S,), bool)  # every slot starts free/idle
        budget = jnp.full((S,), max_new, jnp.int32)
        out_tok = jnp.full((S, max_new), self.pad_id, jnp.int32)
        out_lp = jnp.zeros((S, max_new), jnp.float32)
        t = jnp.zeros((), jnp.int32)
        occ = jnp.zeros((), jnp.int32)

        # host bookkeeping ------------------------------------------------ #
        slot_seq = np.full(S, -1, np.int64)  # dataset row held by each slot
        row_cache_pos = np.zeros(B, np.int64)  # cache offset per episode
        res_tok = np.full((B, max_new), self.pad_id, np.int32)
        res_lp = np.zeros((B, max_new), np.float32)
        res_len = np.zeros((B,), np.int32)
        completed = 0
        refills = 0
        cont_refills = 0
        cont_feed_tokens = 0
        obs_tokens = 0
        total_turns = 0
        tool_calls = 0
        prefill_lane_tokens = 0
        bursts = 0

        burst = self._burst_jit.get((S, smax))
        if burst is None:
            burst = self._burst_jit[(S, smax)] = self._make_burst(S)

        while completed < B:
            with get_tracer().span("rollout/visit", cat="rollout",
                                   visit=bursts) as visit:
                # one bundled host sync per visit: flush every slot's state
                done_h, resp_len_h, out_tok_h, out_lp_h = jax.device_get(
                    (done, resp_len, out_tok, out_lp))
                # flush finished slots: single-turn -> results; env -> step
                # the episode and either finalize or re-enqueue a
                # continuation (KV rows for every continuing slot are
                # gathered in ONE device call after the loop, then sliced per
                # episode)
                pending_conts: List[Tuple[int, int, np.ndarray]] = []
                for s in range(S):
                    if not (done_h[s] and slot_seq[s] >= 0):
                        continue
                    row = slot_seq[s]
                    slot_seq[s] = -1
                    if not env_on:
                        res_tok[row] = out_tok_h[s]
                        res_lp[row] = out_lp_h[s]
                        res_len[row] = resp_len_h[s]
                        completed += 1
                        continue
                    ep = episodes[row]
                    n = int(resp_len_h[s])
                    rtoks = out_tok_h[s, :n].copy()
                    ep.record_turn(rtoks, out_lp_h[s, :n])
                    row_cache_pos[row] += n - 1  # decode steps this turn
                    obs, r, ep_done, info = ep.env.step(rtoks)
                    ep.reward += float(r)
                    ep.turn += 1
                    ep.infos.append(info or {})
                    total_turns += 1
                    if info and info.get("tool_call"):
                        tool_calls += 1
                    if ep_done or ep.turn >= max_turns:
                        completed += 1
                        continue
                    obs = np.asarray(obs, np.int32).ravel()[
                        : self.obs_budget]
                    ep.record_obs(obs)
                    # the last response token's KV was never written (it was
                    # sampled, not fed), so it leads the feed; the saved rows
                    # carry the whole shared prefix — nothing is re-prefilled
                    feed = np.concatenate([rtoks[-1:], obs])
                    pending_conts.append((s, row, feed))
                    cont_feed_tokens += len(feed)
                    obs_tokens += len(obs)
                if pending_conts:
                    gathered = self.model.gather_cache_rows(
                        caches,
                        jnp.asarray([s for s, _, _ in pending_conts],
                                    jnp.int32))
                    for j, (s, row, feed) in enumerate(pending_conts):
                        saved = jax.tree.map(
                            lambda a, j=j: a[:, j:j + 1], gathered)
                        queue.push(_Continuation(
                            row, feed, saved, row_cache_pos[row]))
                        row_cache_pos[row] += len(feed)
                visit.set(completed=completed)
                if completed >= B:
                    break
                # refill every free slot, one jitted call per homogeneous
                # batch (continuations first, then fresh-prompt length
                # buckets)
                free = [s for s in range(S) if slot_seq[s] < 0]
                while free and len(queue):
                    kind, L, items = queue.pop_work(len(free))
                    lanes, free = free[: len(items)], free[len(items):]
                    # pad the batch to the next power of two (capped at the
                    # pool size), not the full pool: a late-stream
                    # single-slot refill runs 1 lane, not num_slots — and a
                    # full-pool fill keeps the exact pool shape, which is
                    # what the lockstep-equivalence schedule runs
                    R = 1
                    while R < len(items):
                        R *= 2
                    R = min(R, S)
                    slots_arr = jnp.asarray(
                        np.concatenate([lanes, np.full(R - len(lanes), S)])
                        .astype(np.int32)
                    )
                    lane_budget = np.full(R, max_new, np.int32)
                    if kind == "prefill":
                        idxs = items
                        batch = np.zeros((R, L), np.int32)
                        batch[: len(idxs)] = queue.prompts[idxs][:, :L]
                        lane_budget[: len(idxs)] = budgets_np[idxs]
                        rk = (k0 if refills == 0
                              else jax.random.fold_in(k0, refills))
                        rf = self._refill_jit.get((R, L, smax))
                        if rf is None:
                            rf = self._refill_jit[(R, L, smax)] = \
                                self._make_refill(R, L, smax)
                        with get_tracer().span("rollout/prefill",
                                               cat="rollout", lanes=R,
                                               width=L, seqs=len(idxs)):
                            (caches, cur_tok, cache_len, resp_len, done,
                             budget, out_tok, out_lp) = rf(
                                params, caches, jnp.asarray(batch), slots_arr,
                                jnp.asarray(lane_budget), rk,
                                cur_tok, cache_len, resp_len, done, budget,
                                out_tok, out_lp,
                            )
                        for lane, seq in zip(lanes, idxs):
                            slot_seq[lane] = seq
                            row_cache_pos[seq] = L
                        refills += 1
                        # count the lanes the prefill actually executed
                        # (incl. the pow2 padding lanes) so prefill_waste
                        # reflects real compute
                        prefill_lane_tokens += R * L
                    else:  # continuation: feed tokens only, saved KV reused
                        feed = np.zeros((R, L), np.int32)
                        start_len = np.zeros(R, np.int64)
                        for j, c in enumerate(items):
                            feed[j] = c.feed
                            start_len[j] = c.cache_len
                            lane_budget[j] = budgets_np[c.row]
                        rows = self._stack_cont_rows(items, R)
                        ck = jax.random.fold_in(k0, 1_000_000 + cont_refills)
                        cf = self._cont_jit.get((R, L, smax))
                        if cf is None:
                            cf = self._cont_jit[(R, L, smax)] = \
                                self._make_continue(R, L, smax)
                        with get_tracer().span("rollout/refill",
                                               cat="rollout", lanes=R,
                                               width=L, conts=len(items)):
                            (caches, cur_tok, cache_len, resp_len, done,
                             budget, out_tok, out_lp) = cf(
                                params, caches, rows, slots_arr,
                                jnp.asarray(feed),
                                jnp.asarray(start_len.astype(np.int32)),
                                jnp.asarray(lane_budget), ck,
                                cur_tok, cache_len, resp_len, done, budget,
                                out_tok, out_lp,
                            )
                        for lane, c in zip(lanes, items):
                            slot_seq[lane] = c.row
                        cont_refills += 1
                if not any(slot_seq[s] >= 0 for s in range(S)):
                    break  # queue drained and nothing in flight
                # a lane refilled immediately-done (EOS at its first token /
                # budget 1) is counted in the burst's n_done_entry, so the
                # loop below won't mistake it for a fresh completion; it
                # flushes on the next visit.
                # "pending" must also count in-flight episodes that may
                # re-enter the queue as continuations — otherwise a drained
                # fresh-prompt queue would hold every finished slot at a
                # global barrier until the slowest turn completes (lockstep
                # turns, zero overlap).
                # Conservative: an episode below its turn cap counts as pending
                # even if its env ends up finishing it (costs one extra host
                # visit). Single-turn runs (env off or max_turns == 1) never
                # have such episodes, so their burst schedule is untouched.
                cont_possible = env_on and max_turns > 1 and any(
                    slot_seq[s] >= 0
                    and episodes[slot_seq[s]].turn + 1 < max_turns
                    for s in range(S)
                )
                has_pending = jnp.asarray(len(queue) > 0 or cont_possible)
                with get_tracer().span("rollout/decode", cat="rollout",
                                       burst=bursts, completed=completed):
                    (caches, cur_tok, cache_len, resp_len, done, budget,
                     out_tok, out_lp, t, occ) = burst(
                        params, caches, cur_tok, cache_len, resp_len, done,
                        budget, out_tok, out_lp, t, occ, step_keys, k2,
                        has_pending,
                    )
                bursts += 1

        # assemble RolloutResult in dataset order ------------------------- #
        with get_tracer().span("rollout/assemble", cat="rollout", seqs=B):
            if not env_on:
                Lmax = Lp + max_new
                tokens = np.concatenate([prompts_np, res_tok], axis=1)
                mask = np.zeros((B, Lmax), bool)
                for b in range(B):
                    mask[b, Lp: Lp + res_len[b]] = True
                old_lp = np.concatenate(
                    [np.zeros((B, Lp), np.float32), res_lp], axis=1)
                roles = None
                total_turns = completed  # one turn per sequence
                self.last_env = None
            else:
                Lmax = (Lp + max_turns * max_new
                        + (max_turns - 1) * self.obs_budget)
                tokens = np.full((B, Lmax), self.pad_id, np.int32)
                tokens[:, :Lp] = queue_rows
                roles = np.zeros((B, Lmax), np.int8)
                old_lp = np.zeros((B, Lmax), np.float32)
                rewards = np.zeros(B, np.float32)
                turns = np.zeros(B, np.int32)
                for b, ep in enumerate(episodes):
                    n = len(ep.toks)
                    tokens[b, Lp: Lp + n] = ep.toks
                    roles[b, Lp: Lp + n] = ep.roles
                    old_lp[b, Lp: Lp + n] = ep.lps
                    rewards[b] = ep.reward
                    turns[b] = ep.turn
                mask = roles == 1
                old_lp = np.where(mask, old_lp, 0.0)
                res_len = mask.sum(axis=1).astype(np.int32)
                self.last_env = {
                    "rewards": rewards,
                    "turns": turns,
                    "tool_calls": tool_calls,
                }

            wall = time.perf_counter() - t_start
            steps = int(jax.device_get(t))
            occ_steps = int(jax.device_get(occ))
            gen_tokens = int(res_len.sum())
            # each turn's first token comes from a refill/continuation sample,
            # not a decode step (single-turn: total_turns == B)
            decode_tokens = gen_tokens - total_turns
            lane_steps = S * steps
            self.last_stats = {
                "tokens": float(gen_tokens),
                "wall_s": wall,
                "tokens_per_s": gen_tokens / wall if wall > 0 else 0.0,
                "decode_steps": float(steps),
                "bursts": float(bursts),
                "refills": float(refills),
                "num_slots": float(S),
                "slot_occupancy": (
                    occ_steps / lane_steps if lane_steps else 1.0),
                "padding_waste": (
                    1.0 - decode_tokens / lane_steps if lane_steps else 0.0),
                "prefill_lane_tokens": float(prefill_lane_tokens),
                "prefill_true_tokens": float(prefill_true_tokens),
                "prefill_waste": (
                    1.0 - prefill_true_tokens / prefill_lane_tokens
                    if prefill_lane_tokens else 0.0),
                # per-turn prefill accounting: turn 1 prefills true prompt
                # tokens; every later turn feeds ONLY the observation plus one
                # carried response token through the decode path (KV reuse —
                # the acceptance metric for the episode loop)
                "prefill_tokens": float(
                    prefill_true_tokens + cont_feed_tokens),
                "prefill_tokens_turn1": float(prefill_true_tokens),
                "prefill_tokens_turn2plus": float(cont_feed_tokens),
                "obs_tokens": float(obs_tokens),
                "cont_refills": float(cont_refills),
                "turns": float(total_turns),
            }
            if env_on:
                self.last_stats["turns_mean"] = (
                    total_turns / B if B else 0.0)
                self.last_stats["tool_calls"] = float(tool_calls)
            return RolloutResult(
                jnp.asarray(tokens),
                jnp.asarray(mask),
                jnp.asarray(old_lp),
                jnp.asarray(res_len.astype(np.int32)),
                None if roles is None else jnp.asarray(roles),
            )


def lockstep_waste(lengths: np.ndarray, max_new: int) -> float:
    """Padding-waste of the lockstep schedule for the same responses: the
    fraction of decode lane-steps (B x (max_new-1)) that produced no counted
    token. The benchmark arm reports this next to the engine's measured
    waste."""
    lengths = np.asarray(lengths)
    B = len(lengths)
    lane_steps = B * max(max_new - 1, 1)
    decode_tokens = int(lengths.sum()) - B
    return 1.0 - decode_tokens / lane_steps if lane_steps else 0.0
