"""Plain float32 reference of DreamerV3 (Hafner et al. 2023) as the program trains it.

One gradient update = world-model update on a (L, B) batch of replayed sequences
(CNN encoder, RSSM with 32x32 discrete latents, deconvolution decoder, two-hot reward
head, continue head, balanced KL with free nats), then imagination for ``horizon`` steps
from every posterior state, an actor update (REINFORCE on percentile-normalised
lambda-returns plus entropy) and a critic update (two-hot NLL of the lambda-returns plus
the regulariser towards the target critic), then the target critic's EMA.  One dispatch =
sampling ``U`` such batches from the replay ring and ``U`` updates in a row.

Everything is ``jax.numpy`` in float32 with matmuls at ``highest`` precision, no
kernels, nothing imported from the program.  Where the program departs from the paper
the reference follows the program and says so (``# program:``).
``precision`` selects how every matmul/convolution reads its operands: ``"f32"`` (the
reference), ``"bf16"``, ``"fp8"`` (per-tensor scaled e4m3: the control, the nearest
precision below the configuration's bf16-mixed).  ``fault="half_batch"`` leaves the second
half of every batch out and takes the means over the rest.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _quantizer(precision: str):
    if precision == "f32":
        return lambda x: x
    if precision == "bf16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        def q(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
            return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return q
    raise ValueError(f"unknown precision {precision!r}")


# ----------------------------------------------------------------------------
# building blocks
# ----------------------------------------------------------------------------

def layer_norm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def dense(p, x, q):
    y = jnp.dot(q(x), q(p["kernel"]), precision=HI)
    return y + p["bias"] if "bias" in p else y


def _ln(p):  # the program wraps flax's LayerNorm in a module of its own: one level of names more
    return p["LayerNorm_0"]


def mlp(p, x, layers, q, head=True):
    """Dense -> LayerNorm(1e-3) -> SiLU blocks, then a plain dense head."""
    for i in range(layers):
        x = jax.nn.silu(layer_norm(_ln(p[f"ln_{i}"]), dense(p[f"dense_{i}"], x, q), 1e-3))
    return dense(p["head"], x, q) if head and "head" in p else x


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)


def log_softmax(x):
    return x - jax.scipy.special.logsumexp(x, -1, keepdims=True)


def unimix_logits(logits, unimix):
    """1% uniform mixture, as log-probabilities."""
    probs = (1.0 - unimix) * jax.nn.softmax(logits, -1) + unimix / logits.shape[-1]
    return log_softmax(jnp.log(probs))


def sample_onehot(logp, key):
    """A one-hot sample with straight-through gradients to the probabilities."""
    hot = jax.nn.one_hot(jax.random.categorical(key, logp), logp.shape[-1], dtype=jnp.float32)
    probs = jnp.exp(logp)
    return hot + probs - lax.stop_gradient(probs)


def twohot_bins(n):
    return jnp.linspace(-20.0, 20.0, n, dtype=jnp.float32)


def twohot_mean(logits):
    return symexp(jnp.sum(jax.nn.softmax(logits, -1) * twohot_bins(logits.shape[-1]), -1))


def twohot_log_prob(logits, value):
    """log-probability of a scalar target under the symlog two-hot encoding."""
    bins = twohot_bins(logits.shape[-1])
    n = bins.shape[0]
    x = jnp.clip(symlog(value), bins[0], bins[-1])
    below = jnp.clip(jnp.sum((bins <= x[..., None]).astype(jnp.int32), -1) - 1, 0, n - 1)
    above = jnp.clip(below + 1, 0, n - 1)
    same = below == above
    d_below = jnp.where(same, 1.0, jnp.abs(bins[below] - x))
    d_above = jnp.where(same, 1.0, jnp.abs(bins[above] - x))
    total = d_below + d_above
    target = (
        jax.nn.one_hot(below, n) * (d_above / total)[..., None]
        + jax.nn.one_hot(above, n) * (d_below / total)[..., None]
    )
    return jnp.sum(target * log_softmax(logits), -1)


# ----------------------------------------------------------------------------
# the world model's parts
# ----------------------------------------------------------------------------

def encode(wm, rgb, q):
    """(N, 64, 64, 3) in [-0.5, 0.5] -> (N, 4096): four stride-2 4x4 convolutions, each LN + SiLU."""
    p = wm["encoder"]
    x = rgb
    for i in range(4):
        x = lax.conv_general_dilated(
            q(x), q(p[f"conv_{i}"]["kernel"]), (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
        )
        x = jax.nn.silu(layer_norm(_ln(p[f"cnn_ln_{i}"]), x, 1e-3))
    return x.reshape(x.shape[0], -1)


def decode(wm, latent, q):
    """(N, 1536) -> (N, 64, 64, 3): a dense layer to 4x4x256, three LN + SiLU deconvolutions, one plain."""
    p = wm["observation_model"]
    x = dense(p["cnn_in"], latent, q)
    x = x.reshape(x.shape[0], 4, 4, -1)
    deconv = lambda layer, y: lax.conv_transpose(  # noqa: E731
        q(y), q(layer["kernel"]), (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI
    )
    for i in range(3):
        x = jax.nn.silu(layer_norm(_ln(p[f"cnn_ln_{i}"]), deconv(p[f"deconv_{i}"], x), 1e-3))
    return deconv(p["deconv_out"], x) + p["deconv_out"]["bias"]


def recurrent(wm, h, x, q):
    """(z, a) -> dense + LN + SiLU -> the LayerNorm GRU cell with its update gate biased by -1."""
    p = wm["recurrent_model"]
    y = jax.nn.silu(layer_norm(_ln(p["ln"]), dense(p["in"], x, q), 1e-3))
    parts = layer_norm(_ln(p["gru"]["ln"]), dense(p["gru"]["fused"], jnp.concatenate([y, h], -1), q), 1e-5)
    reset, cand, update = jnp.split(parts, 3, -1)
    cand = jnp.tanh(jax.nn.sigmoid(reset) * cand)
    update = jax.nn.sigmoid(update - 1.0)
    return update * cand + (1.0 - update) * h


def prior_logits(wm, h, hp, q):
    return mlp(wm["transition_model"], h, 1, q).reshape(h.shape[0], hp["stoch"], hp["discrete"])


def initial_state(wm, batch, hp, q):
    h0 = jnp.broadcast_to(jnp.tanh(wm["initial_recurrent"]), (batch, wm["initial_recurrent"].shape[0]))
    logp = unimix_logits(prior_logits(wm, h0, hp, q), hp["unimix"])
    z0 = jax.nn.one_hot(jnp.argmax(logp, -1), hp["discrete"], dtype=jnp.float32)
    return h0, z0.reshape(batch, -1)


def dynamic(wm, h, z, action, embed, is_first, key, hp, q):
    """One posterior step: reset at episode starts, GRU, prior and posterior logits, a posterior sample."""
    h0, z0 = initial_state(wm, h.shape[0], hp, q)
    keep = 1.0 - is_first
    h, z, action = h * keep + h0 * is_first, z * keep + z0 * is_first, action * keep
    h = recurrent(wm, h, jnp.concatenate([z, action], -1), q)
    prior = prior_logits(wm, h, hp, q)
    post = mlp(wm["representation_model"], jnp.concatenate([h, embed], -1), 1, q)
    post = post.reshape(h.shape[0], hp["stoch"], hp["discrete"])
    z = sample_onehot(unimix_logits(post, hp["unimix"]), key).reshape(h.shape[0], -1)
    return h, z, post, prior


def imagine(wm, h, z, action, key, hp, q):
    h = recurrent(wm, h, jnp.concatenate([z, action], -1), q)
    logp = unimix_logits(prior_logits(wm, h, hp, q), hp["unimix"])
    return h, sample_onehot(logp, key).reshape(h.shape[0], -1)


def actor_logp(actor, latent, hp, q):
    return unimix_logits(dense(actor["head"], mlp(actor["trunk"], latent, hp["mlp_layers"], q, head=False), q),
                         hp["actor_unimix"])


def critic_logits(critic, latent, hp, q):
    return dense(critic["head"], mlp(critic["trunk"], latent, hp["mlp_layers"], q, head=False), q)


# ----------------------------------------------------------------------------
# the world-model loss
# ----------------------------------------------------------------------------

def world_model_loss(wm_params, data, key, hp, q):
    wm = wm_params["params"]
    L, B = data["rewards"].shape
    obs = data["rgb"].astype(jnp.float32) / 255.0 - 0.5
    embed = encode(wm, obs.reshape((L * B,) + obs.shape[2:]), q).reshape(L, B, -1)
    # h_t consumes a_{t-1}; the first row of every sampled sequence starts an episode for the model
    actions = jnp.concatenate([jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], 0)
    is_first = data["is_first"].at[0].set(1.0)[..., None]

    def step(carry, xs):
        h, z = carry
        embed_t, act_t, first_t, k_t = xs
        h, z, post, prior = dynamic(wm, h, z, act_t, embed_t, first_t, k_t, hp, q)
        return (h, z), (h, z, post, prior)

    h0 = jnp.zeros((B, hp["recurrent"]))
    z0 = jnp.zeros((B, hp["stoch"] * hp["discrete"]))
    _, (hs, zs, post, prior) = lax.scan(step, (h0, z0), (embed, actions, is_first, jax.random.split(key, L)))
    latents = jnp.concatenate([zs, hs], -1)
    flat = latents.reshape(L * B, -1)

    recon = decode(wm, flat, q).reshape(obs.shape)
    observation_loss = jnp.sum((recon - obs) ** 2, (-3, -2, -1))
    reward_logits = mlp(wm["reward_model"], flat, hp["mlp_layers"], q).reshape(L, B, -1)
    reward_loss = -twohot_log_prob(reward_logits, data["rewards"])
    cont_logit = mlp(wm["continue_model"], flat, hp["mlp_layers"], q).reshape(L, B)
    cont = 1.0 - data["terminated"]
    continue_loss = hp["continue_scale"] * (jax.nn.softplus(-cont_logit) * cont + jax.nn.softplus(cont_logit) * (1.0 - cont))

    # program: the KL terms read the raw logits (no unimix), as the reference implementation's loss does
    lp, lq = log_softmax(post), log_softmax(prior)
    kl_of = lambda a, b: jnp.sum(jnp.exp(a) * (a - b), -1).sum(-1)  # noqa: E731
    kl = kl_of(lax.stop_gradient(lp), lq)
    kl_loss = hp["kl_dynamic"] * jnp.maximum(kl, hp["free_nats"]) + hp["kl_representation"] * jnp.maximum(
        kl_of(lp, lax.stop_gradient(lq)), hp["free_nats"]
    )
    keep = data["keep"]  # 1 for the rows that count (all of them unless a fault is planted)
    mean = lambda x: jnp.sum(x * keep) / jnp.sum(jnp.broadcast_to(keep, x.shape))  # noqa: E731
    total = mean(hp["kl_regularizer"] * kl_loss + observation_loss + reward_loss + continue_loss)
    entropy = lambda a: -jnp.sum(jnp.exp(a) * a, -1).sum(-1).mean()  # noqa: E731
    aux = {
        "metrics": (total, mean(observation_loss), mean(reward_loss), mean(kl_loss), mean(continue_loss), mean(kl)),
        "entropies": (entropy(lax.stop_gradient(lp)), entropy(lax.stop_gradient(lq))),
        "latents": latents,
    }
    return total, aux


# ----------------------------------------------------------------------------
# behaviour: imagination, actor and critic
# ----------------------------------------------------------------------------

def lambda_values(rewards, values, continues, lam):
    def back(nxt, xs):
        r, v, c = xs
        ret = r + c * ((1 - lam) * v + lam * nxt)
        return ret, ret

    _, rets = lax.scan(back, values[-1], (rewards, values, continues), reverse=True)
    return rets


def adam_update(params, grads, state, hp_opt):
    """clip_by_global_norm, then Adam with bias correction (optax's arithmetic)."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm < hp_opt["clip"], 1.0, hp_opt["clip"] / norm)
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = hp_opt["b1"], hp_opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, state["nu"], grads)
    count = state["count"] + 1
    c1, c2 = 1 - b1 ** count.astype(jnp.float32), 1 - b2 ** count.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, n: p - hp_opt["lr"] * (m / c1) / (jnp.sqrt(n / c2) + hp_opt["eps"]), params, mu, nu
    )
    return params, {"mu": mu, "nu": nu, "count": count}


def behaviour_update(p, opt, latents, terminated, keep, key, hp, q):
    wm = p["world_model"]["params"]
    H = hp["horizon"]
    S = hp["stoch"] * hp["discrete"]
    start = lax.stop_gradient(latents.reshape(-1, latents.shape[-1]))
    n = start.shape[0]
    keep_n = jnp.broadcast_to(keep, terminated.shape).reshape(n)

    def step(carry, k_t):
        h, z = carry
        latent = jnp.concatenate([z, h], -1)
        k_a, k_z = jax.random.split(k_t)
        logp = actor_logp(p["actor"]["params"], latent, hp, q)
        action = sample_onehot(logp, jax.random.split(k_a, 1)[0])
        h, z = imagine(wm, h, z, action, k_z, hp, q)
        return (h, z), (latent, action)

    _, (traj, actions) = lax.scan(step, (start[:, S:], start[:, :S]), jax.random.split(key, H + 1))
    traj, actions = lax.stop_gradient(traj), lax.stop_gradient(actions)
    flat = traj.reshape((H + 1) * n, -1)
    rewards = twohot_mean(mlp(wm["reward_model"], flat, hp["mlp_layers"], q)).reshape(H + 1, n)
    values = twohot_mean(critic_logits(p["critic"]["params"], flat, hp, q)).reshape(H + 1, n)
    continues = (jax.nn.sigmoid(mlp(wm["continue_model"], flat, hp["mlp_layers"], q)[..., 0]) > 0.5).astype(jnp.float32)
    continues = jnp.concatenate([(1.0 - terminated).reshape(1, n), continues.reshape(H + 1, n)[1:]], 0)
    returns = lambda_values(rewards[1:], values[1:], continues[1:] * hp["gamma"], hp["lmbda"])  # (H, n)
    discount = jnp.cumprod(continues * hp["gamma"], 0) / hp["gamma"]

    # percentile normaliser of the returns (an EMA of the 5th and 95th percentile)
    m = hp["moments"]
    counted = returns if keep is None else returns  # quantiles over every imagined return
    low = m["decay"] * p["moments"]["low"] + (1 - m["decay"]) * jnp.quantile(counted, m["low"])
    high = m["decay"] * p["moments"]["high"] + (1 - m["decay"]) * jnp.quantile(counted, m["high"])
    invscale = jnp.maximum(1.0 / m["max"], high - low)
    advantage = (returns - low) / invscale - (values[:-1] - low) / invscale
    weight = discount[:-1] * keep_n[None, :]
    wmean = lambda x: jnp.sum(x * weight) / (H * jnp.sum(keep_n))  # noqa: E731

    def actor_loss(actor_params):
        logp = actor_logp(actor_params["params"], traj, hp, q)[:-1]
        log_prob = jnp.sum(actions[:-1] * logp, -1)
        entropy = -jnp.sum(jnp.exp(logp) * logp, -1)
        return -wmean(log_prob * advantage + hp["ent_coef"] * entropy)

    policy_loss, a_grads = jax.value_and_grad(actor_loss)(p["actor"])
    new_actor, opt_actor = adam_update(p["actor"], a_grads, opt["actor"], hp["opt"]["actor"])

    # critic: two-hot NLL of the lambda-returns plus the regulariser towards the target critic's mean
    flat_sg = traj[:-1].reshape(H * n, -1)
    target_mean = twohot_mean(critic_logits(p["target_critic"]["params"], flat_sg, hp, q)).reshape(H, n)

    def critic_loss(critic_params):
        logits = critic_logits(critic_params["params"], flat_sg, hp, q).reshape(H, n, -1)
        nll = -twohot_log_prob(logits, returns) - twohot_log_prob(logits, target_mean)
        return wmean(nll)

    value_loss, c_grads = jax.value_and_grad(critic_loss)(p["critic"])
    new_critic, opt_critic = adam_update(p["critic"], c_grads, opt["critic"], hp["opt"]["critic"])
    p = dict(p, actor=new_actor, critic=new_critic, moments={"low": low, "high": high})
    opt = dict(opt, actor=opt_actor, critic=opt_critic)
    return p, opt, policy_loss, value_loss


def single_update(p, opt, data, key, hp, q):
    k_wm, k_beh = jax.random.split(key)
    (_, aux), grads = jax.value_and_grad(world_model_loss, has_aux=True)(p["world_model"], data, k_wm, hp, q)
    new_wm, opt_wm = adam_update(p["world_model"], grads, opt["world_model"], hp["opt"]["world_model"])
    p, opt = dict(p, world_model=new_wm), dict(opt, world_model=opt_wm)
    p, opt, policy_loss, value_loss = behaviour_update(
        p, opt, aux["latents"], data["terminated"], data["keep"], k_beh, hp, q
    )
    # program: the target critic moves towards the critic after every update (update frequency 1)
    tau = hp["tau"]
    p = dict(p, target_critic=jax.tree.map(lambda t, c: (1 - tau) * t + tau * c, p["target_critic"], p["critic"]))
    return p, opt, aux["metrics"] + (policy_loss, value_loss) + aux["entropies"]


def sample_indices(key, filled, total, L):
    """Contiguous sequences from a ring that has not wrapped: an env in proportion to its rows,
    a start uniform over its valid range."""
    weights = jnp.where(filled >= L, filled, 0).astype(jnp.float32)
    logits = jnp.where(weights > 0, jnp.log(jnp.maximum(weights, 1e-9)), -jnp.inf)
    k_env, k_start = jax.random.split(key)
    env = jax.random.categorical(k_env, logits, shape=(total,))
    start = jax.random.randint(k_start, (total,), 0, jnp.maximum(jnp.take(filled - L, env) + 1, 1))
    return start[:, None] + jnp.arange(L)[None, :], env


@partial(jax.jit, static_argnames=("hp_static", "n_samples", "precision", "fault"))
def dispatch(p, opt, ring, filled, key, hp_static, n_samples, precision="f32", fault=None):
    """``n_samples`` updates on batches drawn from ``ring`` (leaves (rows, envs, ...), in stored order)."""
    hp = _thaw(hp_static)
    q = _quantizer(precision)
    B, L = hp["batch"], hp["seq_len"]
    k_sample, k_train = jax.random.split(key)
    t_idx, env = sample_indices(k_sample, filled, B * n_samples, L)
    blocks = {}
    for name, buf in ring.items():
        g = buf[t_idx, env[:, None]].reshape((n_samples, B, L) + buf.shape[2:])
        blocks[name] = jnp.swapaxes(g, 1, 2)
    keep = jnp.ones((1, B)) if fault != "half_batch" else (jnp.arange(B) < B // 2).astype(jnp.float32)[None]
    data = {
        "rgb": blocks["rgb"], "actions": blocks["actions"].astype(jnp.float32),
        "rewards": blocks["rewards"][..., 0], "terminated": blocks["terminated"][..., 0],
        "is_first": blocks["is_first"][..., 0],
    }

    def body(carry, xs):
        p, opt = carry
        block, k = xs
        p, opt, metrics = single_update(p, opt, dict(block, keep=keep), k, hp, q)
        return (p, opt), metrics

    (p, opt), metrics = lax.scan(body, (p, opt), (data, jax.random.split(k_train, n_samples)))
    return p, opt, tuple(m.mean() for m in metrics)


def _thaw(frozen):
    return {k: (_thaw(v) if isinstance(v, tuple) and v and isinstance(v[0], tuple) else v) for k, v in frozen}


def freeze(tree):
    """A nested dict of numbers as a hashable tuple (a static argument of ``dispatch``)."""
    return tuple(sorted((k, freeze(v) if isinstance(v, dict) else v) for k, v in tree.items()))


# ----------------------------------------------------------------------------
# the player: one env-interaction step
# ----------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("hp_static", "precision"))
def player_step(p, carry, rgb, key, hp_static, precision="f32"):
    """(h, z, a) and a picture in [-0.5, 0.5] -> the next carry and the sampled one-hot action."""
    hp = _thaw(hp_static)
    q = _quantizer(precision)
    wm = p["world_model"]["params"]
    h, z, prev_action = carry
    k_repr, k_act, k_next = jax.random.split(key, 3)
    embed = encode(wm, rgb, q)
    h, z, _, _ = dynamic(wm, h, z, prev_action, embed, jnp.zeros((h.shape[0], 1)), k_repr, hp, q)
    logp = actor_logp(p["actor"]["params"], jnp.concatenate([z, h], -1), hp, q)
    k_branch = jax.random.split(k_act, 1)[0]  # program: one key per action branch; this env has one
    perturbed = logp + jax.random.gumbel(k_branch, logp.shape, logp.dtype)  # categorical() takes its argmax
    return (h, z, sample_onehot(logp, k_branch)), perturbed, k_next
