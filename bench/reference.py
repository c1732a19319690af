"""Plain float32 reference of one GRPO step on the benchmark's models.

It imports nothing of the program and takes nothing the program has made.
It builds its own weights from the seed, following the initialisation the
configuration file's ``layout`` states, and reads only the token ids, masks
and answers of the rollouts. Then, in float32 at the highest matmul
precision, it computes:

- per-token log-probabilities and entropies: the hidden states come from
  the model of the layout's kind (``bench/models/<kind>.py``, found by
  :func:`bench.catalog.model`); the head and its statistics are computed
  here, in chunks of positions;
- the reward of the synthetic math task, GRPO's group-relative advantages,
  and the GRPO loss (clipped surrogate, k3 KL to the frozen reference,
  entropy bonus);
- clipping by global norm and the AdamW update. The parameters are kept in
  bfloat16, as the configuration stores them, and m and v in float32.

``precision="fp8"`` rounds every matmul operand to float8 (e4m3, one scale
per tensor). That is the control: the reference one precision step below the
bfloat16 the configuration computes in. ``half_batch=True`` takes the loss
over the first half of the rows only, the mean over the rest: one of the
planted faults the comparison must catch.

Everything runs in blocks of rows (and the head in chunks of positions), so
that it fits one chip after the program's state is freed.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import catalog
from bench.models._common import _mm

HEAD_CHUNK = 256
ROWS = 1  # rows in one block of the forward and of the gradient
NEG = -1e30  # logit of a padded vocabulary slot: weight 0, finite products


# --------------------------------------------------------------------------- #
# the model, from the file its layout's kind names
# --------------------------------------------------------------------------- #
def arch_of(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file: those every
    kind has, and those of the kind its ``layout.kind`` names."""
    lay = config["layout"]
    return dict(kind=lay["kind"], layers=lay["num_layers"], d=lay["d_model"],
                vocab=lay["vocab_size"], vocab_padded=lay["padded_vocab"],
                tied=lay["tie_embeddings"], eps=lay["norm_eps"],
                **catalog.model(lay["kind"]).arch(lay))


@functools.partial(jax.jit, static_argnums=0)
def _init(a_items, key):
    a = dict(a_items)
    return catalog.model(a["kind"]).init(a, key)


def init_params(a: dict, seed: int):
    """Initial parameters: the stated scheme, keyed by the first of three
    subkeys of ``PRNGKey(seed)``, in one jitted call on the device."""
    actor_key = jax.random.split(jax.random.PRNGKey(seed), 3)[0]
    return _init(tuple(sorted(a.items())), actor_key)


def _token_stats(a, params, tokens, pr):
    """(log-prob, entropy), each (b, S): position i scores tokens[:, i] given
    the tokens before it; position 0 reads 0."""
    h = catalog.model(a["kind"]).hidden(a, params, tokens, pr)
    head = params["embed"].T if a["tied"] else params["lm_head"]
    real = jnp.arange(a["vocab_padded"]) < a["vocab"]
    b, S, d = h.shape
    hs, labels = h[:, :-1], tokens[:, 1:]
    pad = (-hs.shape[1]) % HEAD_CHUNK
    hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
    labels = jnp.pad(labels, ((0, 0), (0, pad)))
    nc = hs.shape[1] // HEAD_CHUNK

    @jax.checkpoint
    def chunk(_, xs):
        hx, lx = xs
        logits = jnp.where(real, _mm(hx, head, pr), NEG)
        logz = jax.nn.logsumexp(logits, -1)
        lp = jnp.take_along_axis(logits, lx[..., None], -1)[..., 0] - logz
        ent = logz - jnp.sum(jax.nn.softmax(logits, -1) * logits, -1)
        return None, (lp, ent)

    split = lambda t: jnp.moveaxis(t.reshape(b, nc, HEAD_CHUNK, *t.shape[2:]),
                                   1, 0)
    _, (lp, ent) = jax.lax.scan(chunk, None, (split(hs), split(labels)))
    join = lambda t: jnp.moveaxis(t, 0, 1).reshape(b, -1)[:, :S - 1]
    zero = jnp.zeros((b, 1), jnp.float32)
    return (jnp.concatenate([zero, join(lp)], 1),
            jnp.concatenate([zero, join(ent)], 1))


# --------------------------------------------------------------------------- #
# the task's reward and GRPO's advantages
# --------------------------------------------------------------------------- #
DIGIT0, EOS = 3 + ord("0"), 2  # byte tokens are shifted by 3 specials


def math_reward(tokens: np.ndarray, mask: np.ndarray,
                answers: np.ndarray) -> np.ndarray:
    """1.0 when the response opens with the answer's decimal digits followed
    by EOS; otherwise 0.1 for each leading digit that matches."""
    out = np.zeros(len(tokens), np.float32)
    L = tokens.shape[1]
    for r in range(len(tokens)):
        first = int(np.argmax(mask[r]))
        at = lambda off: int(tokens[r, min(first + off, L - 1)])
        want = [int(c) for c in str(int(answers[r]))]
        ok = []
        for i, dgt in enumerate(want):
            ok.append((not ok or ok[-1]) and at(i) == DIGIT0 + dgt)
        if ok[-1] and at(len(want)) == EOS:
            out[r] = 1.0
        else:
            out[r] = 0.1 * sum(ok)
    return out


def grpo_advantages(rewards: np.ndarray, mask: np.ndarray,
                    group: int) -> np.ndarray:
    g = rewards.reshape(-1, group).astype(np.float64)
    adv = (g - g.mean(1, keepdims=True)) / (g.std(1, keepdims=True) + 1e-6)
    return (adv.reshape(-1)[:, None] * mask).astype(np.float32)


# --------------------------------------------------------------------------- #
# one GRPO step
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnums=(0, 3))
def _stats_block(a_items, params, tokens, pr):
    return _token_stats(dict(a_items), params, tokens, pr)


@functools.partial(jax.jit, static_argnums=(0, 5, 6, 7), donate_argnums=(2,))
def _grad_block(a_items, params, acc, block, count, pr, clip_eps, coefs):
    """acc + d(block's share of the loss)/d(params), in float32."""
    a = dict(a_items)
    kl_coef, ent_coef = coefs

    def loss(p32):
        lp, ent = _token_stats(a, p32, block["tokens"], pr)
        m = block["mask"]
        ratio = jnp.exp(lp - block["old_lp"])
        adv = block["adv"]
        surr = jnp.minimum(ratio * adv,
                           jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv)
        delta = block["ref_lp"] - lp
        kl = jnp.exp(delta) - delta - 1.0
        total = jnp.sum((-surr + kl_coef * kl - ent_coef * ent) * m)
        return total / count

    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    val, g = jax.value_and_grad(loss)(p32)
    return jax.tree.map(jnp.add, acc, g), val


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, step, lr, b1, b2, eps, max_norm):
    """Clip by global norm, then AdamW; the parameters and moments are
    donated, so the update runs in place. Also returns the clipped
    gradient's norm per leaf."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m_, v_: (p.astype(jnp.float32) - lr * (m_ / c1) / (
            jnp.sqrt(v_ / c2) + eps)).astype(p.dtype), params, m, v)
    return params, m, v, [jnp.sqrt(jnp.sum(g * g))
                          for g in jax.tree.leaves(grads)]


@jax.jit
def leaf_norms(tree):
    """The float32 norm of every leaf, in tree order."""
    return [jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32))))
            for t in jax.tree.leaves(tree)]


@jax.jit
def diff_norms(a, b):
    """The float32 norm of each leaf's difference, in tree order."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def token_stats(a: dict, params, tokens: np.ndarray,
                precision: str = "f32") -> Tuple[np.ndarray, np.ndarray]:
    """Log-probs and entropies of every row, ``ROWS`` at a time."""
    items = tuple(sorted(a.items()))
    lps, ents = [], []
    for i in range(0, len(tokens), ROWS):
        lp, ent = _stats_block(items, params, jnp.asarray(tokens[i:i + ROWS]),
                               precision)
        lps.append(np.asarray(lp))
        ents.append(np.asarray(ent))
    return np.concatenate(lps), np.concatenate(ents)


def follow(a: dict, seed: int, steps: List[dict], rl: dict, *,
           precision: str = "f32", half_batch: bool = False) -> dict:
    """Run the reference through ``steps`` (each: ``tokens``, ``mask``,
    ``answers`` of one iteration's rollouts) from the seed's initial
    parameters. Returns, per step, the log-probs under the policy that
    generated it (``old_lp``) and under the frozen initial policy
    (``ref_lp``), and the loss; the first step's clipped gradient norm per
    leaf; and the norm of each leaf's change over all the steps."""
    items = tuple(sorted(a.items()))
    params = init_params(a, seed)
    ref_lp = [token_stats(a, params, s["tokens"], precision)[0]
              for s in steps]
    m = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), params)
    v = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), params)
    out = {"old_lp": [], "ref_lp": ref_lp, "loss": [], "reward": []}
    for i, s in enumerate(steps):
        tokens, mask = s["tokens"], s["mask"].astype(np.float32)
        old_lp, _ = token_stats(a, params, tokens, precision)
        rewards = math_reward(tokens, s["mask"], s["answers"])
        adv = grpo_advantages(rewards, mask, rl["group_size"])
        keep = len(tokens) // 2 if half_batch else len(tokens)
        count = max(float(mask[:keep].sum()), 1.0)
        acc = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), params)
        loss = 0.0
        for r in range(0, keep, ROWS):
            sl = slice(r, min(r + ROWS, keep))
            block = {"tokens": jnp.asarray(tokens[sl]),
                     "mask": jnp.asarray(mask[sl]),
                     "old_lp": jnp.asarray(old_lp[sl]),
                     "ref_lp": jnp.asarray(ref_lp[i][sl]),
                     "adv": jnp.asarray(adv[sl])}
            acc, val = _grad_block(items, params, acc, block,
                                   jnp.float32(count), precision,
                                   float(rl["clip_eps"]),
                                   (float(rl["kl_coef"]),
                                    float(rl["entropy_coef"])))
            loss += float(val)
        params, m, v, clipped_norms = _adamw(
            params, acc, m, v, jnp.float32(i + 1), jnp.float32(rl["lr"]),
            jnp.float32(0.9), jnp.float32(0.95), jnp.float32(1e-8),
            jnp.float32(rl["max_grad_norm"]))
        if i == 0:
            out["grad_norms"] = np.asarray(clipped_norms)
        out["old_lp"].append(old_lp)
        out["loss"].append(loss)
        out["reward"].append(rewards)
    del m, v
    out["delta_norms"] = np.asarray(diff_norms(params, init_params(a, seed)))
    out["leaf_names"] = leaf_names(params)
    return out


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(k) for k, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


# --------------------------------------------------------------------------- #
# the numbers compared
# --------------------------------------------------------------------------- #
def leaf_gaps(got: np.ndarray, want: np.ndarray,
              keep: np.ndarray) -> np.ndarray:
    """Per kept leaf, |got - want| / max(want, median of want): the gap
    between the two norms, never the norm of a difference."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = float(np.median(want[keep])) if keep.any() else 0.0
    gap = np.abs(got - want) / np.maximum(np.maximum(want, floor), 1e-30)
    return gap[keep]


def compare(program: dict, ref: dict) -> Dict[str, float]:
    """The numbers the benchmark holds to limits. ``program`` carries what
    the timed path produced in the same steps: ``old_lp``, ``ref_lp`` and
    ``mask`` per step, ``loss`` per step, the first step's gradient norms as
    the optimizer got them, and each leaf's change after the steps."""
    gen, refg = 0.0, 0.0
    for i, mask in enumerate(program["mask"]):
        sel = mask.astype(bool)
        gen = max(gen, float(np.max(np.abs(
            program["old_lp"][i] - ref["old_lp"][i])[sel], initial=0.0)))
        refg = max(refg, float(np.max(np.abs(
            program["ref_lp"][i] - ref["ref_lp"][i])[sel], initial=0.0)))
    loss = max(abs(p - r) / max(abs(r), 1e-30)
               for p, r in zip(program["loss"], ref["loss"]))
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: a rule on the reference's gradient, not on names
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    moved = g_ref >= 1e-3 * np.median(g_ref)
    names = np.asarray(ref["leaf_names"])[moved]
    grad = leaf_gaps(program["grad_norms"], g_ref, moved)
    upd = leaf_gaps(program["delta_norms"], ref["delta_norms"], moved)
    return {"gen_logprob_gap": gen, "ref_logprob_gap": refg,
            "loss_rel_gap": loss, "grad_leaf_gap": float(grad.max()),
            "update_leaf_gap": float(upd.max()),
            "update_median_leaf_gap": float(np.median(upd)),
            "worst_grad_leaf": str(names[grad.argmax()]),
            "worst_update_leaf": str(names[upd.argmax()])}
