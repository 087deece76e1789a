"""Plain float32 reference of the PPO iteration, independent of the program.

It reads the benchmark's weights (``weights.py``) and the tokens the program
served, and follows the program's first iterations: reference and reward
scores, the actor's logprobs, the critic's values, then the minibatched PPO
updates of actor and critic under AdamW.  The model itself (its forward
pass and LM head) is the architecture family's (``chipbench/archs``); the
PPO and AdamW arithmetic here is shared.  Every computation is in float32
and every matrix product runs at ``Precision.HIGHEST``.  What is stored
follows the configuration: parameters in their served type (bfloat16, the
value head float32), AdamW's moments in the traffic's ``state_dtype`` over
a float32 master copy, so that an update smaller than a bfloat16 step moves
the master and not the weights, as it does in the program.  ``dot="fp8"``
is the control: the same computation with every matmul operand rounded to
float8 (e4m3, per-tensor scale), the step below the configuration's
bfloat16.  ``fault="half_batch"`` plants one
fault in it: each minibatch loss is the mean over half of its rows.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _q8(x):
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def make_dot(kind: str):
    if kind == "fp32":
        return functools.partial(jnp.einsum, precision=HIGHEST)
    if kind == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, _q8(a), _q8(b),
                                             precision=HIGHEST)
    raise ValueError(f"dot {kind!r} not in ('fp32', 'fp8')")


@dataclasses.dataclass(frozen=True)
class PPO:
    """PPO and AdamW settings, as the traffic file states them."""
    gamma: float
    lam: float
    clip_eps: float
    value_clip: float
    kl_coef: float
    n_minibatches: int
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float
    state_dtype: str


# ------------------------------------------------------------------ model

def logprobs(family, p, arch, tokens, gen_start, dot):
    """Logprob of each generated token (B, S - gen_start); one row of
    vocabulary logits at a time."""
    hid = family.forward(p, arch, tokens, dot)[:, gen_start - 1:-1]
    tgt = tokens[:, gen_start:]
    head = family.lm_head(p)

    @jax.checkpoint
    def row(args):
        h, t = args
        lg = dot("td,vd->tv", h, head)
        lp = jax.nn.log_softmax(lg, axis=-1)
        return jnp.take_along_axis(lp, t[:, None], axis=-1)[:, 0]

    return jax.lax.map(row, (hid, tgt))


def values(family, p, arch, tokens, gen_start, dot):
    """Values at positions gen_start-1 .. S-1 (B, T+1)."""
    hid = family.forward(p, arch, tokens, dot)[:, gen_start - 1:]
    return dot("btd,do->bto", hid, p["value_head"]["w"])[..., 0]


# ------------------------------------------------------------------ PPO

def advantages(hp: PPO, reward, logp, ref_logp, vals):
    """KL-shaped token rewards with the sequence reward on the last token,
    GAE over (B, T) with the (B, T+1) values, then whitening.  Every
    generated token is valid (no EOS).  Returns (adv, ret)."""
    r = -hp.kl_coef * (logp - ref_logp)
    r = r.at[:, -1].add(reward)
    v, v_next = vals[:, :-1], vals[:, 1:]
    delta = r + hp.gamma * v_next - v

    def back(carry, d):
        carry = d + hp.gamma * hp.lam * carry
        return carry, carry

    _, adv = jax.lax.scan(back, jnp.zeros(r.shape[0], jnp.float32), delta.T,
                          reverse=True)
    adv = adv.T
    ret = adv + v
    adv = (adv - adv.mean()) * jax.lax.rsqrt(adv.var() + 1e-8)
    return adv, ret


def _adamw(hp: PPO, p, opt, g):
    """One AdamW step on the float32 master; the weights are the master
    rounded to their stored type, the moments are stored in
    ``hp.state_dtype``."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, hp.grad_clip / jnp.maximum(gn, 1e-12))
    step = opt["step"] + 1
    bc1 = 1.0 - hp.b1 ** step
    bc2 = 1.0 - hp.b2 ** step
    f32 = jnp.float32
    m = jax.tree.map(lambda m, g: hp.b1 * m.astype(f32)
                     + (1 - hp.b1) * g * scale, opt["m"], g)
    v = jax.tree.map(lambda v, g: hp.b2 * v.astype(f32)
                     + (1 - hp.b2) * jnp.square(g * scale), opt["v"], g)
    master = jax.tree.map(
        lambda w, m, v: w - hp.lr * ((m / bc1) / (jnp.sqrt(v / bc2) + hp.eps)
                                     + hp.weight_decay * w),
        opt["master"], m, v)
    sd = jnp.dtype(hp.state_dtype)
    return (jax.tree.map(lambda w, q: w.astype(q.dtype), master, p),
            {"step": step, "master": master,
             "m": jax.tree.map(lambda x: x.astype(sd), m),
             "v": jax.tree.map(lambda x: x.astype(sd), v)})


def _rows(fault, *xs):
    """The rows a minibatch loss averages over: all, or half under the
    planted fault."""
    if fault == "half_batch":
        return tuple(x[: x.shape[0] // 2] for x in xs)
    return xs


class Reference:
    """Jitted reference programs for one configuration and one precision;
    ``family`` is the architecture's module (``chipbench/archs``)."""

    def __init__(self, family, arch, hp: PPO, gen_start: int, dot="fp32",
                 fault=None):
        self.arch, self.hp, self.gen_start = arch, hp, gen_start
        d = make_dot(dot)
        g = gen_start
        self.logprobs = jax.jit(
            lambda p, t: logprobs(family, _f32(p), arch, t, g, d))
        self.values = jax.jit(
            lambda p, t: values(family, _f32(p), arch, t, g, d))
        self.advantages = jax.jit(functools.partial(advantages, hp))

        def actor_loss(p, tok, old, adv):
            tok, old, adv = _rows(fault, tok, old, adv)
            new = logprobs(family, p, arch, tok, g, d)
            ratio = jnp.exp(jnp.clip(new - old, -20.0, 20.0))
            clipped = jnp.clip(ratio, 1 - hp.clip_eps, 1 + hp.clip_eps)
            return -jnp.mean(jnp.minimum(ratio * adv, clipped * adv))

        def critic_loss(p, tok, old, ret):
            tok, old, ret = _rows(fault, tok, old, ret)
            new = values(family, p, arch, tok, g, d)[:, :-1]
            clipped = old + jnp.clip(new - old, -hp.value_clip, hp.value_clip)
            return 0.5 * jnp.mean(jnp.maximum(jnp.square(new - ret),
                                              jnp.square(clipped - ret)))

        def update(loss_fn):
            def step(p, opt, *batch):
                # the gradient at the stored weights, in float32
                loss, grads = jax.value_and_grad(loss_fn)(_f32(p), *batch)
                p, opt = _adamw(hp, p, opt, grads)
                return p, opt, loss
            return jax.jit(step, donate_argnums=(0, 1))

        self.actor_update = update(actor_loss)
        self.critic_update = update(critic_loss)

    def train(self, update, p, opt, *batch):
        """The minibatched PPO update: one AdamW step per row block, in
        order.  Returns (params, opt, mean minibatch loss)."""
        n = self.hp.n_minibatches
        rows = batch[0].shape[0] // n
        losses = []
        for k in range(n):
            sl = slice(k * rows, (k + 1) * rows)
            p, opt, loss = update(p, opt, *(x[sl] for x in batch))
            losses.append(loss)
        return p, opt, float(np.mean([float(x) for x in losses]))


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _opt_init(p, state_dtype):
    zeros = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jnp.zeros(x.shape, state_dtype), t)
    # a copy even where the weights are float32, so no buffer is donated
    # twice
    master = jax.tree.map(lambda x: jnp.array(x, jnp.float32, copy=True), p)
    return {"step": jnp.zeros((), jnp.int32), "m": zeros(p), "v": zeros(p),
            "master": master}


def leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda t: [jnp.linalg.norm(x.astype(jnp.float32))
                               for x in t])([x for _, x in flat])
    return {jax.tree_util.keystr(k): float(n)
            for (k, _), n in zip(flat, norms)}


def diff_norms(a, b) -> dict:
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree.leaves(b)
    norms = jax.jit(lambda x, y: [
        jnp.linalg.norm(u.astype(jnp.float32) - v.astype(jnp.float32))
        for u, v in zip(x, y)])([x for _, x in fa], fb)
    return {jax.tree_util.keystr(k): float(n) for (k, _), n in zip(fa, norms)}


def follow(ref: Reference, make_weights, seqs, *, offload=False) -> dict:
    """Follow the program's iterations over the tokens it served.

    ``make_weights()`` returns the benchmark's {"lm", "value"} weights
    afresh; ``seqs`` is one (B, S) token array per iteration.  Returns, per
    iteration, the actor's logprobs, the reference logprobs, values,
    rewards and both mean minibatch losses; the per-leaf norms of both
    AdamW first moments after the first iteration; and the per-leaf norms
    of each model's parameter change after the last.  ``offload`` keeps
    the idle model's AdamW moments on the host."""
    with jax.default_matmul_precision("highest"):
        w = make_weights()
        toks = [jnp.asarray(s) for s in seqs]
        ref_logp = [np.asarray(ref.logprobs(w["lm"], t)) for t in toks]
        rewards = [np.asarray(ref.values(w["value"], t)[:, -1]) for t in toks]
        # actor starts as the frozen policy, critic as the reward model
        actor, critic = w["lm"], w["value"]
        del w
        sd = jnp.dtype(ref.hp.state_dtype)
        a_opt, c_opt = _opt_init(actor, sd), _opt_init(critic, sd)
        out = {"logp": [], "ref_logp": ref_logp, "values": [],
               "rewards": rewards, "actor_loss": [], "critic_loss": []}
        a0 = jax.device_get(actor)
        c0 = jax.device_get(critic)
        park = (lambda t: jax.device_get(t)) if offload else (lambda t: t)
        c_opt = park(c_opt)
        for it, t in enumerate(toks):
            logp = ref.logprobs(actor, t)
            vals = ref.values(critic, t)
            adv, ret = ref.advantages(rewards[it], logp, ref_logp[it], vals)
            out["logp"].append(np.asarray(logp))
            out["values"].append(np.asarray(vals))
            a_opt = jax.device_put(a_opt)
            actor, a_opt, la = ref.train(ref.actor_update, actor, a_opt,
                                         t, logp, adv)
            a_opt = park(a_opt)
            c_opt = jax.device_put(c_opt)
            critic, c_opt, lc = ref.train(ref.critic_update, critic, c_opt,
                                          t, vals[:, :-1], ret)
            c_opt = park(c_opt)
            out["actor_loss"].append(la)
            out["critic_loss"].append(lc)
            if it == 0:
                out["actor_m"] = leaf_norms(a_opt["m"])
                out["critic_m"] = leaf_norms(c_opt["m"])
        out["actor_change"] = diff_norms(a_opt["master"], a0)
        out["critic_change"] = diff_norms(c_opt["master"], c0)
    return out
