"""Plain float32 reference of a dense decoder's training step.

Written from the published architecture, independent of the program
under test (it imports nothing of ``repro``): token embedding, a stack
of pre-norm layers (RMSNorm, LayerNorm or non-parametric LayerNorm;
causal GQA attention with RoPE and an optional QKV bias; a SwiGLU MLP),
a final norm, the tied (or untied) LM head and the mean token
cross-entropy, then AdamW with global-norm clipping and a linear
warm-up.  Every matmul runs at ``Precision.HIGHEST`` (a float32 matmul
on a TPU otherwise runs in bfloat16), and parameters are stored after
each update in the dtype the configuration states, as the program
stores them.

It is computed in blocks so that it fits on the chip beside nothing
else: the layers go forward one at a time, keeping only each layer's
input, and backward one at a time, recomputing the layer; attention
runs over blocks of query rows and the loss over blocks of tokens.  The
gradient is taken with respect to float32 copies of one layer's
weights at a time, so no float32 copy of the whole model is ever live.
Over several devices each holds whole rows of the batch and the
gradients are summed across them.

``compute`` computes the whole step, but for the sum of the embedding
lookup's gradient, in a lower-precision type, with the parameters and
Adam kept in float32 (mixed precision); ``fault`` plants a fault in the
reference put in the program's place: ``"half_batch"`` drops the second
half of each device's tokens from the loss and gradient,
``"no_exchange"`` leaves the gradient sum across devices out.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flops import dims

Q_BLOCK = 512    # query rows per attention block
T_BLOCK = 512    # tokens per block of the LM head and loss
HIGHEST = lax.Precision.HIGHEST


# ---------------------------------------------------------------- weights --

def _norm_shapes(cfg: dict, lead: tuple) -> dict:
    d = cfg["hidden_size"]
    if cfg["norm"] == "rmsnorm":
        return {"scale": lead + (d,)}
    if cfg["norm"] == "ln":
        return {"scale": lead + (d,), "bias": lead + (d,)}
    if cfg["norm"] == "ln_nonparam":
        return {}
    raise ValueError(f"unknown norm {cfg['norm']!r}")


def param_shapes(cfg: dict) -> dict:
    """The parameter tree: layers stacked on a leading axis."""
    g = dims(cfg)
    D, H, K, dh, F, V, L = (g[k] for k in ("D", "H", "K", "dh", "F", "V",
                                           "L"))
    attn = {"wq": (L, D, H * dh), "wk": (L, D, K * dh),
            "wv": (L, D, K * dh), "wo": (L, H * dh, D)}
    if cfg["qkv_bias"]:
        attn.update(bq=(L, H * dh), bk=(L, K * dh), bv=(L, K * dh))
    tree = {"embed": (V, D),
            "layers": {"norm_attn": _norm_shapes(cfg, (L,)), "attn": attn,
                       "norm_mlp": _norm_shapes(cfg, (L,)),
                       "mlp": {"w_gate": (L, D, F), "w_up": (L, D, F),
                               "w_down": (L, F, D)}},
            "final_norm": _norm_shapes(cfg, ())}
    if not cfg["tie_word_embeddings"]:
        tree["lm_head"] = (V, D)
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def abstract_params(cfg: dict) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dtype),
                        param_shapes(cfg), is_leaf=_is_shape)


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative integer (wider than 32 bits too)."""
    a, b = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(a) & 0x7FFFFFFF),
                              int(b) & 0x7FFFFFFF)


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Seeded weights in the configuration's dtype: embeddings N(0, 0.02),
    matrices N(0, 1/fan_in), biases N(0, 0.02), norm scales 1 + N(0, 0.02).
    Jit it; each leaf draws from its own fold of the key."""
    dtype = jnp.dtype(cfg["dtype"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in ("embed", "lm_head") or name.startswith("b"):
            x = x * 0.02
        elif name == "scale":
            x = 1.0 + x * 0.02
        elif name == "bias":
            x = x * 0.02
        else:
            x = x / math.sqrt(shape[-2])
        leaves.append(x.astype(dtype))
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------- forward --

def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def _norm(x, p: dict, cfg: dict):
    eps = cfg["norm_eps"]
    if cfg["norm"] == "rmsnorm":
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    out = (x - mu) * lax.rsqrt(var + eps)
    if cfg["norm"] == "ln":
        out = out * p["scale"] + p["bias"]
    return out


def _rope(x, theta: float):
    """Rotary embedding on (B, S, heads, dh), halves rotated together."""
    S, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _attention(q, k, v):
    """Causal attention, q (B,S,H,dh), k/v (B,S,K,dh), over query blocks."""
    B, S, H, dh = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = Q_BLOCK if S % Q_BLOCK == 0 else S
    nb = S // qb
    blocks = jnp.swapaxes(q.reshape(B, nb, qb, H, dh), 0, 1)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = _mm("bqhd,bkhd->bhqk", qi, k) / math.sqrt(dh)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(block, (jnp.arange(nb), blocks))
    return jnp.swapaxes(out, 0, 1).reshape(B, S, H, dh)


def _layer(x, lp: dict, cfg: dict):
    g = dims(cfg)
    B, S, _ = x.shape
    a = lp["attn"]
    h = _norm(x, lp["norm_attn"], cfg)
    q = _mm("bsd,de->bse", h, a["wq"])
    k = _mm("bsd,de->bse", h, a["wk"])
    v = _mm("bsd,de->bse", h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(B, S, g["H"], g["dh"]), cfg["rope_theta"])
    k = _rope(k.reshape(B, S, g["K"], g["dh"]), cfg["rope_theta"])
    v = v.reshape(B, S, g["K"], g["dh"])
    o = _attention(q, k, v).reshape(B, S, g["H"] * g["dh"])
    x = x + _mm("bse,ed->bsd", o, a["wo"])
    m = lp["mlp"]
    h = _norm(x, lp["norm_mlp"], cfg)
    u = jax.nn.silu(_mm("bsd,df->bsf", h, m["w_gate"])) \
        * _mm("bsd,df->bsf", h, m["w_up"])
    return x + _mm("bsf,fd->bsd", u, m["w_down"])


def _head_loss(x, final_norm: dict, head, labels, mask, cfg: dict):
    """Summed cross-entropy of the kept tokens, over blocks of tokens."""
    D = x.shape[-1]
    n = x.shape[0] * x.shape[1]
    tb = T_BLOCK if n % T_BLOCK == 0 else n
    xs = _norm(x, final_norm, cfg).reshape(n // tb, tb, D)
    ls = labels.reshape(n // tb, tb)
    ms = mask.reshape(n // tb, tb)

    @jax.checkpoint
    def block(carry, args):
        xb, lb, mb = args
        logits = _mm("td,vd->tv", xb, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(jnp.where(mb, lse - picked, 0.0)), None

    total, _ = lax.scan(block, jnp.zeros((), jnp.float32), (xs, ls, ms))
    return total


# --------------------------------------------------------------- gradient --

def _local_loss_and_grads(params, tokens, labels, cfg: dict,
                          fault: str | None, axis: str, dt):
    """Mean loss over the batch's kept tokens and its gradient on every
    leaf (in ``dt``, float32 but for a control), for the rows this
    device holds."""
    B, S = tokens.shape
    mask = jnp.ones((B, S), bool)
    if fault == "half_batch":
        mask = (jnp.arange(B * S) < (B * S) // 2).reshape(B, S)
    n_local = jnp.sum(mask).astype(jnp.float32)
    n_total = n_local if fault == "no_exchange" else lax.psum(n_local, axis)
    tied = "lm_head" not in params
    embed32 = params["embed"].astype(dt)
    x0 = jnp.take(embed32, tokens, axis=0)

    def layer(x, lp):
        return _layer(x, _cast(lp, dt), cfg)

    def fwd(x, lp):
        return layer(x, lp), x

    xL, xs = lax.scan(fwd, x0, params["layers"])

    head = embed32 if tied else params["lm_head"].astype(dt)

    def top(x, fnorm, hd):
        return _head_loss(x, fnorm, hd, labels, mask, cfg) / n_total

    loss, top_vjp = jax.vjp(top, xL, _cast(params["final_norm"], dt), head)
    g_x, g_fnorm, g_head = top_vjp(jnp.ones((), jnp.float32))

    if fault == "no_exchange":
        def sync(tree):
            return tree
    else:
        def sync(tree):    # summed layer by layer, so one layer's is live
            return jax.tree.map(lambda g: lax.psum(g, axis), tree)

    def bwd(gx, args):
        x, lp = args
        _, vjp = jax.vjp(lambda xx, pc: _layer(xx, pc, cfg), x,
                         _cast(lp, dt))
        gx, g_lp = vjp(gx)
        return gx, sync(g_lp)

    g_x0, g_layers = lax.scan(bwd, g_x, (xs, params["layers"]),
                              reverse=True)
    # the lookup's gradient is summed in float32 whatever ``dt`` is: in
    # bfloat16 a frequent token's row stops growing once it is large
    g_embed = jnp.zeros(embed32.shape, jnp.float32).at[
        tokens.reshape(-1)].add(
        g_x0.reshape(-1, g_x0.shape[-1]).astype(jnp.float32))
    top_grads = {"embed": g_embed + g_head if tied else g_embed,
                 "final_norm": g_fnorm}
    if not tied:
        top_grads["lm_head"] = g_head
    grads = dict(sync(top_grads), layers=g_layers)
    if fault == "no_exchange":
        # each device keeps its own mean gradient; the loss is still
        # the mean over devices, as the program reports it
        return lax.pmean(loss, axis), grads
    return lax.psum(loss, axis), grads


# ------------------------------------------------------------------ AdamW --

def adamw(params, grads, mu, nu, step, opt: dict):
    """One AdamW step: clip the global norm to ``grad_clip``, warm the
    rate up linearly over ``warmup_steps``, decay every leaf stored with
    two or more dimensions, store in each leaf's own dtype.  Returns
    (params, mu, nu, clip factor)."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    t = step + 1
    lr = opt["lr"] * min(1.0, t / opt["warmup_steps"])
    c1 = 1.0 - opt["b1"] ** t
    c2 = 1.0 - opt["b2"] ** t

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * clip
        m = opt["b1"] * m + (1 - opt["b1"]) * g
        v = opt["b2"] * v + (1 - opt["b2"]) * g * g
        u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        p32 = p.astype(jnp.float32)
        decay = opt["weight_decay"] * p32 if p.ndim >= 2 else 0.0
        return (p32 - lr * (u + decay)).astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), clip


def leaf_norms(tree) -> dict:
    """{leaf name: float32 L2 norm} (jit it)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {leaf_name(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


def change_norms(new, old) -> dict:
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old))


# The precision below the one a configuration states: the control is the
# program's own model in it.
LOWER = {"float32": "bfloat16"}


# --------------------------------------------------------------- the run --

class Reference:
    """The reference's first ``len(batches)`` training steps on
    ``devices``: each device takes an equal share of every batch's rows.
    ``compute`` and ``fault`` make the mixed-precision step and the
    planted faults described at the top of this module."""

    def __init__(self, cfg: dict, opt: dict, devices,
                 fault: str | None = None, compute: str = "float32"):
        self.cfg, self.opt = cfg, opt
        self.mesh = Mesh(np.asarray(devices), ("rows",))
        rep = NamedSharding(self.mesh, P())
        self.rows = NamedSharding(self.mesh, P("rows"))

        ab = abstract_params(cfg)
        pspec = jax.tree.map(lambda _: P(), ab)
        body = partial(_local_loss_and_grads, cfg=cfg, fault=fault,
                       axis="rows", dt=jnp.dtype(compute))
        self._grads = jax.jit(jax.shard_map(
            body, mesh=self.mesh, in_specs=(pspec, P("rows"), P("rows")),
            out_specs=(P(), pspec), check_vma=False))
        self._init = jax.jit(partial(init_params, cfg), out_shardings=rep)
        self._adam = jax.jit(partial(adamw, opt=opt), static_argnums=(4,),
                             donate_argnums=(0, 2, 3))
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(change_norms)
        self._zeros = jax.jit(lambda p: jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), p))

    def run(self, seed: int, batches: list) -> dict:
        """Losses of each step, the norm of each leaf of the first
        gradient before it is clipped, and the norm of each leaf's
        change over all the steps."""
        with jax.default_matmul_precision("highest"):
            params = self._init(seed_key(seed))
            mu, nu = self._zeros(params), self._zeros(params)
            losses, grad_norms = [], None
            for step, b in enumerate(batches):
                tok = jax.device_put(b["tokens"], self.rows)
                lab = jax.device_put(b["labels"], self.rows)
                loss, grads = self._grads(params, tok, lab)
                losses.append(float(loss))
                if step == 0:
                    grad_norms = {k: float(v) for k, v in
                                  self._norms(grads).items()}
                params, mu, nu, _ = self._adam(params, grads, mu, nu, step)
                del grads
            del mu, nu
            p0 = self._init(seed_key(seed))
            change = {k: float(v) for k, v in self._change(params, p0).items()}
            del params, p0
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change}
