"""Plain float32 reference of a dense decoder-only LM and its AdamW steps.

Written in plain ``jax.numpy`` from the configuration's numbers, with no
kernels, no caches and nothing imported from the program.  All matrix
products run under ``jax.default_matmul_precision("highest")``.

The block is the one the configurations state as they are run: pre-norm
RMSNorm (eps 1e-6, f32), grouped-query attention with half-split rotary
position embedding over the whole head, a causal softmax scaled by
1/sqrt(head_dim), a SiLU-gated MLP, a final RMSNorm and an LM head (tied to
the embedding or not).  The loss is the mean next-token cross entropy plus
1e-4 x the mean squared log-partition (z-loss).  Each configuration file
lists where this departs from the published model.

The reference runs layer by layer (``lax.scan`` over the stacked layers,
each layer recomputed in the backward pass) and the LM head over blocks of
tokens, so that the weights, the gradients and AdamW's moments of a cell
fit on one chip beside it.  Where they do not, ``train(..., devices=...)``
shards them, and the batch, over those chips, and ``jit`` partitions the
same program.

``init_params`` also makes the weights that the benchmark hands to the
program: the same tree, from the same seed, in one jitted call.

``train(..., num=...)`` puts a lower precision at every matrix product's
operands and output, in both directions: ``Int4``, the control of the
S2FP8 cells, and ``S2FP8``, the paper's format at the sites where the
program truncates, with the program's delayed statistics or fresh ones;
``control.py --emulate`` compares the latter with the f32 reference.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6
ZLOSS = 1e-4
HEAD_BLOCK = 512          # tokens per block of the LM head

# a configuration file's published keys, by the names used here and in
# the program
KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
        "num_key_value_heads": "kv_heads", "intermediate_size": "d_ff",
        "vocab_size": "vocab", "num_hidden_layers": "n_layers",
        "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta"}


def sizes(config: Dict) -> Dict:
    """The sizes that run, from a configuration file: its published keys
    (``head_dim`` is hidden_size / num_attention_heads where the file
    gives none) and its ``block``, the program's settings of the block
    it runs (activation, norm, remat)."""
    out = {name: config[key] for key, name in KEYS.items()}
    out["rope_theta"] = float(out["rope_theta"])
    out["head_dim"] = config.get(
        "head_dim", config["hidden_size"] // config["num_attention_heads"])
    out.update(config["block"])
    return out


# ---------------------------------------------------------------------------
# what the harness asks of a reference: the program this configuration needs,
# the counts its readers use, its CPU test sizes and its checks of a file
# ---------------------------------------------------------------------------

# the program's ``ArchConfig`` fields that must equal the sizes that run
PROGRAM_FIELDS = ("d_model", "n_heads", "kv_heads", "head_dim", "d_ff",
                  "vocab", "n_layers", "tie_embeddings", "rope_theta",
                  "activation", "norm", "remat")

# the CPU test sizes: every width shrunk, the block as the cells run it
TINY = dict(d_model=128, n_heads=4, kv_heads=2, head_dim=32, d_ff=256,
            vocab=512, n_layers=2, rope_theta=10000.0,
            activation="silu_glu", norm="rms", remat=True)


def program_fields(s: Dict) -> Dict:
    """The program's configuration fields (a dotted name for a field of a
    nested group) and the values they must have for sizes ``s``: the
    sizes themselves, and every layer a dense block."""
    out = {k: s[k] for k in PROGRAM_FIELDS}
    out["resolved_pattern"] = ("dense",) * s["n_layers"]
    return out


def count_module():
    """The module whose functions count this architecture's operations
    and bytes for the readers (``counts.py`` at the benchmark's root)."""
    import counts
    return counts


def tiny_sizes(s: Dict) -> Dict:
    """``TINY`` with the embedding tied or untied as in ``s``."""
    return dict(TINY, tie_embeddings=s["tie_embeddings"])


def check_config(config: Dict) -> None:
    """Raises ``ValueError`` where a configuration file's published keys
    do not give the sizes that run: each key of ``KEYS`` read as is, and
    the head size the file's own ``head_dim`` or else hidden_size /
    num_attention_heads."""
    s = sizes(config)
    wrong = {key: (config[key], s[name]) for key, name in KEYS.items()
             if s[name] != config[key]}
    head = config.get("head_dim",
                      config["hidden_size"] / config["num_attention_heads"])
    if s["head_dim"] != head:
        wrong["head_dim"] = (head, s["head_dim"])
    if wrong:
        raise ValueError(f"{config['name']}: file and sizes differ: {wrong}")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def param_shapes(s: Dict) -> Dict:
    """Shapes and init scales of every leaf, in the tree the program uses:
    per-layer leaves stacked on a leading layer axis in one segment."""
    d, hd, ff, L = s["d_model"], s["head_dim"], s["d_ff"], s["n_layers"]
    q, kv = s["n_heads"] * hd, s["kv_heads"] * hd
    ones = None                                  # a norm scale: all ones
    seg = {
        "ln1": {"scale": ((L, d), ones)},
        "wq": ((L, d, q), d ** -0.5), "wk": ((L, d, kv), d ** -0.5),
        "wv": ((L, d, kv), d ** -0.5), "wo": ((L, q, d), q ** -0.5),
        "ln2": {"scale": ((L, d), ones)},
        "mlp": {"w_gate": ((L, d, ff), d ** -0.5),
                "w_up": ((L, d, ff), d ** -0.5),
                "w_down": ((L, ff, d), ff ** -0.5)},
    }
    tree = {"embed": ((s["vocab"], d), 0.02),
            "final_norm": {"scale": ((d,), ones)},
            "segments": [seg]}
    if not s["tie_embeddings"]:
        tree["head"] = ((d, s["vocab"]), d ** -0.5)
    return tree


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def layout(s: Dict, devices):
    """Shardings over ``devices`` of the weights (AdamW's moments alike)
    and of a batch: each leaf split along its last axis where that divides
    by the number of chips, else whole on each; a batch split by rows.
    ``(None, None)`` for one chip."""
    if devices is None or len(devices) == 1:
        return None, None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(devices), ("chips",))
    n = len(devices)

    def leaf(spec):
        shape = spec[0]
        split = ("chips",) if shape[-1] % n == 0 else (None,)
        return NamedSharding(mesh, PartitionSpec(*(None,) * (len(shape) - 1),
                                                 *split))

    return (jax.tree_util.tree_map(leaf, param_shapes(s), is_leaf=_is_spec),
            NamedSharding(mesh, PartitionSpec("chips")))


def init_params(s: Dict, key, shardings=None):
    """f32 weights from ``key``: normal(0, scale) per leaf, norm scales 1;
    made where ``shardings`` (a tree like the weights') puts them."""
    spec = param_shapes(s)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_spec)

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(key):
        out = []
        for i, (shape, scale) in enumerate(leaves):
            if scale is None:
                out.append(jnp.ones(shape, jnp.float32))
            else:
                k = jax.random.fold_in(key, i)
                out.append(jax.random.normal(k, shape, jnp.float32) * scale)
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * scale


def _rope(x, theta):
    """x: [B, heads, S, hd], rotated by position along S."""
    s, hd = x.shape[-2], x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _plain(site, x):
    return x


def _layer(s, q, x, p):
    """One block.  ``q(site, x)`` is applied to every operand and output
    of a matrix product, each under its site's name (``_plain`` for the
    f32 reference)."""
    b, t, d = x.shape
    h, kvh, hd = s["n_heads"], s["kv_heads"], s["head_dim"]
    g = h // kvh

    def mm(name, a, w):
        return q(name + ".out", jnp.dot(q(name + ".a", a), q(name + ".b", w)))

    xn = _rms(x, p["ln1"]["scale"])
    q_ = mm("wq", xn, p["wq"]).reshape(b, t, h, hd).transpose(0, 2, 1, 3)
    k = mm("wk", xn, p["wk"]).reshape(b, t, kvh, hd).transpose(0, 2, 1, 3)
    v = mm("wv", xn, p["wv"]).reshape(b, t, kvh, hd).transpose(0, 2, 1, 3)
    q_ = _rope(q_, s["rope_theta"]).reshape(b, kvh, g, t, hd)
    k = _rope(k, s["rope_theta"])
    scores = q("scores", jnp.einsum("bkgqd,bksd->bkgqs", q("q", q_),
                                    q("k", k))) / math.sqrt(hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(causal, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = q("attn.out", jnp.einsum("bkgqs,bksd->bkgqd", q("probs", probs),
                                 q("v", v)))
    o = o.reshape(b, h, t, hd).transpose(0, 2, 1, 3).reshape(b, t, h * hd)
    x = x + mm("wo", o, p["wo"])
    xn = _rms(x, p["ln2"]["scale"])
    m = p["mlp"]
    y = jax.nn.silu(mm("w_gate", xn, m["w_gate"])) * mm("w_up", xn,
                                                         m["w_up"])
    return x + mm("w_down", y, m["w_down"])


def loss_fn(params, tokens, labels, s: Dict, num=None, stats=None):
    """Mean next-token cross entropy + z-loss, in f32.  ``num`` is a
    lower-precision numerics (``Int4``, ``S2FP8``) put at the sites of
    ``_layer`` and at ``embed`` (the table before the lookup) and
    ``head.*`` (the LM head's product); ``stats`` its per-site state."""
    if num is None:
        q_top = _plain
        layer_q = lambda st: _plain                          # noqa: E731
    else:
        q_top = num.sites(stats["top"] if stats else None)
        layer_q = num.sites
    x = q_top("embed", params["embed"])[tokens]
    seg = params["segments"][0]
    seg_stats = stats["layers"] if stats else None

    @jax.checkpoint
    def body(x, p_st):
        p, st = p_st
        return _layer(s, layer_q(st), x, p), None

    x, _ = jax.lax.scan(body, x, (seg, seg_stats))
    x = _rms(x, params["final_norm"]["scale"])
    w = params["embed"].T if s["tie_embeddings"] else params["head"]
    d = x.shape[-1]
    n = x.shape[0] * x.shape[1]
    # blocks of tokens, unless the numerics takes statistics over the
    # whole logits tensor (S2FP8's are per tensor)
    blk = n if num is not None and num.per_tensor else math.gcd(HEAD_BLOCK, n)

    @jax.checkpoint
    def head(xl):
        xb, lb = xl
        logits = q_top("head.out", jnp.dot(q_top("head.a", xb),
                                           q_top("head.b", w)))
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]
        return jnp.sum(logz - gold), jnp.sum(logz * logz)

    nll, zz = jax.lax.map(head, (x.reshape(n // blk, blk, d),
                                 labels.reshape(n // blk, blk)))
    return jnp.sum(nll) / n + ZLOSS * jnp.sum(zz) / n


# ---------------------------------------------------------------------------
# AdamW steps
# ---------------------------------------------------------------------------

def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


@functools.lru_cache(maxsize=None)
def _step_fn(sizes_items, opt_items, num, devices=None):
    s, o = dict(sizes_items), dict(opt_items)
    keep, _ = layout(s, devices)

    def step(params, m, v, t, tokens, labels, stats):
        with jax.default_matmul_precision("highest"):
            loss, (grads, fresh) = jax.value_and_grad(loss_fn, (0, 5))(
                params, tokens, labels, s, num, stats)
        if num is not None and num.refresh:
            stats = fresh                 # the sites' cotangents: see S2FP8
        gnorms = _norms(grads)
        gnorm = jnp.sqrt(sum(jnp.square(n) for n in
                             jax.tree_util.tree_leaves(gnorms)))
        if o["clip_norm"]:
            c = jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-9))
            grads = jax.tree_util.tree_map(lambda g: g * c, grads)
        b1, b2, lr = o["b1"], o["b2"], o["lr"]
        c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m,
                                   grads)
        v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v,
                                   grads)
        params = jax.tree_util.tree_map(
            lambda p, a, b: p - (lr * (a / c1) / (jnp.sqrt(b / c2) + o["eps"])
                                 + lr * o["weight_decay"] * p),
            params, m, v)
        if keep is not None:
            params, m, v = (jax.lax.with_sharding_constraint(t, keep)
                            for t in (params, m, v))
        return params, m, v, loss, gnorms, stats

    return jax.jit(step, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _change_fn(sizes_items):
    s = dict(sizes_items)

    @jax.jit
    def change(params, key):
        return _norms(jax.tree_util.tree_map(jnp.subtract, params,
                                             init_params(s, key)))

    return change


def change_norms(params, s: Dict, key):
    """Per-leaf norm of ``params`` minus the initial weights of ``key``,
    made again from the seed (so the initial copy need not be kept)."""
    return _change_fn(tuple(sorted(s.items())))(params, key)


def train(s: Dict, opt: Dict, key, batches, num=None,
          half_batch: bool = False, devices=None):
    """AdamW steps from the weights of ``key`` over ``batches``.

    Returns the loss of each step, the per-leaf norm of the first
    gradient (before the clip) and the per-leaf norm of the weights'
    change after the last step.  ``num`` puts a lower precision at every
    site (``Int4``, ``S2FP8``); ``half_batch`` drops the second half of
    each batch's rows (a fault, for calibrating the comparison).
    ``devices``: the chips over which the weights, the moments and each
    batch are sharded (``layout``)."""
    devices = tuple(devices) if devices is not None else None
    shard, rows = layout(s, devices)
    params = init_params(s, key, shard)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    stats = num.init_stats(s) if num is not None else None
    losses, first = [], None
    for i, batch in enumerate(batches):
        tok, lab = batch["tokens"], batch["labels"]
        if half_batch:
            tok, lab = tok[: tok.shape[0] // 2], lab[: lab.shape[0] // 2]
        if rows is not None:
            tok, lab = jax.device_put((tok, lab), rows)
        step = _step_fn(tuple(sorted(s.items())), tuple(sorted(opt.items())),
                        num if num is None else num.at_step(i), devices)
        params, m, v, loss, gnorms, stats = step(
            params, m, v, jnp.float32(i + 1), tok, lab, stats)
        losses.append(float(loss))
        if first is None:
            first = jax.device_get(gnorms)
    del m, v
    change = jax.device_get(change_norms(params, s, key))
    return {"losses": losses, "grad_norms": first, "change_norms": change}


# ---------------------------------------------------------------------------
# lower precisions at the sites of ``_layer`` and ``loss_fn``
# ---------------------------------------------------------------------------

LAYER_SITES = tuple(f"{w}.{r}" for w in ("wq", "wk", "wv", "wo", "w_gate",
                                         "w_up", "w_down")
                    for r in ("a", "b", "out")) + ("q", "k", "v", "attn.out")
TOP_SITES = ("embed", "head.a", "head.b", "head.out")


def _int4(x):
    """Symmetric per-tensor int4: 15 levels scaled by the largest |x|."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 7.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -7, 7) * scale


@jax.custom_vjp
def int4(x):
    """int4 on the value going forward and on its cotangent going back."""
    return _int4(x)


int4.defvjp(lambda x: (_int4(x), None), lambda _, g: (_int4(g),))


class Int4(NamedTuple):
    """The control: int4 at every site, the attention scores and
    probabilities included; the LM head in blocks of tokens."""
    per_tensor = False
    refresh = False

    def init_stats(self, s):
        return None

    def at_step(self, i):
        return self

    def sites(self, st):
        return lambda site, x: int4(x)


def _e5m2(y):
    """Round to the paper's FP8 (1 sign, 5 exponent, 2 mantissa bits;
    subnormals down to 2^-16; largest 57344), to nearest, ties to even."""
    a = jnp.abs(y)
    _, e = jnp.frexp(a)                   # a = f 2^e, f in [0.5, 1)
    step = jnp.ldexp(jnp.ones_like(a), jnp.maximum(e - 1, -14) - 2)
    return jnp.sign(y) * jnp.minimum(jnp.round(a / step) * step, 57344.0)


def _s2fp8_stats(x):
    """Paper Eq. 3-4 over the nonzero elements: with mu and m the mean
    and the largest log2|x|, alpha = 15 / (m - mu), beta = -alpha mu.  A
    tensor of one magnitude is shifted only; an all-zero one is kept."""
    a = jnp.abs(x)
    nz = a > 0
    lg = jnp.log2(jnp.where(nz, a, 1.0))
    n = jnp.sum(nz)
    mu = jnp.sum(jnp.where(nz, lg, 0.0)) / jnp.maximum(n, 1)
    top = jnp.max(jnp.where(nz, lg, -jnp.inf))
    flat = top - mu < 1e-6
    alpha = jnp.where(flat, 1.0, 15.0 / jnp.where(flat, 1.0, top - mu))
    beta = jnp.where(flat, 15.0 - top, -alpha * mu)
    return jnp.where(n > 0, alpha, 1.0), jnp.where(n > 0, beta, 0.0)


def _s2fp8_round(x, alpha, beta):
    """Paper Eq. 5: y = 2^(alpha log2|x| + beta), rounded to FP8, mapped
    back; zeros stay zero.  Stale statistics saturate at the largest
    FP8 value."""
    a = jnp.abs(x)
    nz = a > 0
    y = _e5m2(jnp.exp2(alpha * jnp.log2(jnp.where(nz, a, 1.0)) + beta))
    back = jnp.exp2((jnp.log2(jnp.where(y > 0, y, 1.0)) - beta) / alpha)
    return jnp.where(nz & (y > 0), jnp.sign(x) * back, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _s2fp8_site(x, st, refresh):
    return _s2fp8_site_fwd(x, st, refresh)[0]


def _s2fp8_site_fwd(x, st, refresh):
    af, bf = _s2fp8_stats(x) if refresh else (st[0], st[1])
    return _s2fp8_round(x, af, bf), (af, bf, st[2], st[3])


def _s2fp8_site_bwd(refresh, res, g):
    af, bf, ab, bb = res
    if refresh:
        ab, bb = _s2fp8_stats(g)
        new = jnp.stack([af, bf, ab, bb])
    else:
        new = jnp.zeros(4, jnp.float32)
    return _s2fp8_round(g, ab, bb), new


_s2fp8_site.defvjp(_s2fp8_site_fwd, _s2fp8_site_bwd)


class S2FP8(NamedTuple):
    """S2FP8 at the sites where the program's payload path truncates:
    the embedding table; every linear's input, weight and output; q, k
    and v after the rotary embedding and the attention output (not the
    scores or probabilities, which stay inside the fused attention); the
    LM head's input, weight (the untruncated table, where tied) and
    logits.  Each site takes per-tensor statistics (alpha, beta) for its
    value and for its cotangent.

    They are taken afresh on the steps where ``step % refresh_every ==
    0`` and kept for the others, as a delayed-statistics bank does
    (``refresh_every=1``: fresh on every step, as the paper).  A site's
    state is [alpha, beta] of the value then of the cotangent; on a
    fresh step the site hands its new state back as the cotangent of its
    state argument, so one ``value_and_grad`` gives the gradients and
    the new states."""
    refresh_every: int = 1
    refresh: bool = True
    per_tensor = True

    def init_stats(self, s):
        z = jnp.zeros
        return {"top": {k: z(4, jnp.float32) for k in TOP_SITES},
                "layers": {k: z((s["n_layers"], 4), jnp.float32)
                           for k in LAYER_SITES}}

    def at_step(self, i):
        return self._replace(refresh=i % self.refresh_every == 0)

    def sites(self, st):
        def q(site, x):
            if site in ("scores", "probs"):
                return x
            return _s2fp8_site(x, st[site], self.refresh)
        return q
