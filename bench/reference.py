"""Plain reference of one benchmark training step.

The model's loss and gradients, the NDSC codec (random-sign Hadamard
embedding, per-chunk l-inf scale, R-bit nearest-level quantization) with
error feedback, the mean over workers, global-norm clipping and AdamW,
written in straightforward jax.numpy from the published equations
(xLSTM: arXiv:2405.04517; Llama-style decoder: arXiv:2403.04652; NDSC:
arXiv:2103.07578) and the program's documented conventions. It imports
nothing of the program and takes none of its arrays: weights come from
`bench.weights`, tokens are the step's inputs.

Conventions taken over from the program, each of which a faithful
implementation has to share to be compared at all:
  * the sLSTM's recurrent matrix is per head, (H, dh, 4dh), and its output
    is laid out head-major before the split into the i, f, z, o gates;
  * the mLSTM keys are scaled by dh^-1/2, its stabilizer starts at -1e30;
  * the loss is the mean cross-entropy over the padded vocabulary
    (vocabulary rounded up to a multiple of 256, as the head is stored);
  * the codec's chunk i of leaf j is embedded with the signs drawn from
    fold_in(key(0), j) (the shared frame rule), 256 entries per chunk;
  * the mean over workers is summed left to right.

`dtype=float32` runs every matrix product at "highest" precision: the
reference. The control, which the comparison has to reject, is the
nearest precision below the configuration's: `dtype=bfloat16` at default
precision for float32 at default precision, float32 at "high" (three
bfloat16 passes) for float32 at "highest".
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------
def rmsnorm(x, w, eps):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def scan_in_blocks(step, init, xs, block: int = 64):
    """lax.scan over time in blocks of `block` steps, each recomputed in
    the backward pass: the residuals of one block, not of the whole
    sequence, are live at a time."""
    n = jax.tree.leaves(xs)[0].shape[0]
    block = math.gcd(n, block)
    xb = jax.tree.map(lambda a: a.reshape((n // block, block) + a.shape[1:]),
                      xs)

    @jax.checkpoint
    def run_block(carry, x):
        return jax.lax.scan(step, carry, x)

    carry, ys = jax.lax.scan(run_block, init, xb)
    return carry, ys.reshape((n,) + ys.shape[2:])


def mlstm(p, x, heads):
    b, s, _ = x.shape
    dh = p["wq"].shape[1] // heads
    q = (x @ p["wq"]).reshape(b, s, heads, dh)
    k = (x @ p["wk"]).reshape(b, s, heads, dh) * dh ** -0.5
    v = (x @ p["wv"]).reshape(b, s, heads, dh)
    i_pre = x @ p["wi"]
    f_pre = x @ p["wf"]
    o = jax.nn.sigmoid(x @ p["wo"])
    dt = x.dtype

    def step(carry, inp):
        c, n, m = carry
        qt, kt, vt, it, ft = inp
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + m, it)
        ig = jnp.exp(it - m_new)
        fg = jnp.exp(logf + m - m_new)
        c = (fg[..., None, None] * c
             + ig[..., None, None] * (vt[..., :, None] * kt[..., None, :]))
        n = fg[..., None] * n + ig[..., None] * kt
        num = jnp.einsum("bhvk,bhk->bhv", c, qt)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, qt)), 1.0)
        return (c, n, m_new), num / den[..., None]

    init = (jnp.zeros((b, heads, dh, dh), dt), jnp.zeros((b, heads, dh), dt),
            jnp.full((b, heads), -1e30, dt))
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, i_pre, f_pre))
    _, hs = scan_in_blocks(step, init, xs)
    h = jnp.moveaxis(hs, 0, 1).reshape(b, s, heads * dh)
    return o * (h @ p["w_out"])


def slstm(p, x, heads):
    b, s, d = x.shape
    dh = d // heads
    pre_in = x @ p["w_in"]
    r = p["r_rec"]

    def step(carry, pre_t):
        c, n, h = carry
        rec = jnp.einsum("bhk,hkj->bhj", h.reshape(b, heads, dh), r)
        pre = pre_t + rec.reshape(b, 4 * d)
        i_pre, f_pre, z_pre, o_pre = jnp.split(pre, 4, axis=-1)
        ig = jnp.exp(jnp.minimum(i_pre, 10.0))
        fg = jax.nn.sigmoid(f_pre)
        c = fg * c + ig * jnp.tanh(z_pre)
        n = fg * n + ig
        h = jax.nn.sigmoid(o_pre) * c / jnp.maximum(jnp.abs(n), 1.0)
        return (c, n, h), h

    zero = jnp.zeros((b, d), x.dtype)
    _, hs = scan_in_blocks(step, (zero, zero, zero),
                           jnp.moveaxis(pre_in, 1, 0))
    return jnp.moveaxis(hs, 0, 1) @ p["w_out"]


def xlstm_pair(cfg, p, h):
    heads, eps = cfg["num_heads"], cfg["norm_eps"]
    mp = {k[len("mlstm/"):]: v for k, v in p.items() if k.startswith("mlstm/")}
    sp = {k[len("slstm/"):]: v for k, v in p.items() if k.startswith("slstm/")}
    h = h + mlstm(mp, rmsnorm(h, p["m_norm"], eps), heads)
    return h + slstm(sp, rmsnorm(h, p["s_norm"], eps), heads)


def rope(x, theta):
    s, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def attention(cfg, p, x, block_q=512):
    """Causal softmax attention with grouped KV heads, computed a block of
    query rows at a time so the scores never exceed (B, H, block_q, S)."""
    b, s, _ = x.shape
    hq, hk = cfg["num_heads"], cfg["num_kv_heads"]
    dh = cfg.get("head_dim") or cfg["d_model"] // hq
    q = rope((x @ p["wq"]).reshape(b, s, hq, dh), cfg["rope_theta"])
    k = rope((x @ p["wk"]).reshape(b, s, hk, dh), cfg["rope_theta"])
    v = (x @ p["wv"]).reshape(b, s, hk, dh)
    k = jnp.repeat(k, hq // hk, axis=2)
    v = jnp.repeat(v, hq // hk, axis=2)
    q = q * dh ** -0.5
    nb = -(-s // block_q)
    qb = jnp.pad(q, ((0, 0), (0, nb * block_q - s), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(b, nb, block_q, hq, dh), 1, 0)

    @jax.checkpoint
    def rows(args):
        i, qi = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k).astype(F32)
        qpos = i * block_q + jnp.arange(block_q)
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        sc = jnp.where(mask, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    o = jax.lax.map(rows, (jnp.arange(nb), qb))
    o = jnp.moveaxis(o, 0, 1).reshape(b, nb * block_q, hq * dh)[:, :s]
    return o @ p["wo"]


def attn_mlp(cfg, p, h):
    eps = cfg["norm_eps"]
    h = h + attention(cfg, p, rmsnorm(h, p["attn_norm"], eps))
    x = rmsnorm(h, p["mlp_norm"], eps)
    return h + (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


BLOCKS = {"xlstm_pair": xlstm_pair, "attn_mlp": attn_mlp}


def loss(cfg, params: dict, tokens, dtype=F32):
    """Mean next-token cross-entropy. params: {leaf name: array}, the block
    leaves stacked over layers under 'blocks/…'."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    blocks = {k[len("blocks/"):]: v for k, v in p.items()
              if k.startswith("blocks/")}
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    h = p["embed"][inp]
    block = BLOCKS[cfg["block"]]

    @jax.checkpoint
    def layer(h, bp):
        return block(cfg, bp, h), None

    h, _ = jax.lax.scan(layer, h, blocks)
    h = rmsnorm(h, p["final_norm"], cfg["norm_eps"])
    logits = (h @ p["head"]).astype(F32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# NDSC codec
# ---------------------------------------------------------------------------
def frame_signs(leaf_idx: int, chunk: int) -> jax.Array:
    key = jax.random.fold_in(jax.random.key(0), leaf_idx)
    k_signs, _ = jax.random.split(key)
    return jax.random.rademacher(k_signs, (chunk,), dtype=jnp.int8).astype(F32)


def fwht(x):
    """Normalized Walsh-Hadamard transform of the last axis: at stage h,
    entry j and j+h (bit h of j clear) become (a + b, a - b)."""
    n = x.shape[-1]
    lane = jnp.arange(n)
    h = 1
    while h < n:
        up = jnp.roll(x, -h, axis=-1)
        down = jnp.roll(x, h, axis=-1)
        x = jnp.where((lane & h) == 0, x + up, down - x)
        h *= 2
    return x * (1.0 / math.sqrt(n))


def to_chunks(x, chunk):
    flat = x.astype(F32).reshape(-1)
    c = -(-flat.size // chunk)
    return jnp.pad(flat, (0, c * chunk - flat.size)).reshape(c, chunk)


def quantize(y, bits):
    """Per-row l-inf scale and the index of the nearest of the 2^bits
    levels -1 + (2i+1)/2^bits."""
    m = 2 ** bits
    scale = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    z = y / jnp.maximum(scale, jnp.finfo(F32).tiny)
    idx = jnp.floor((jnp.clip(z, -1.0, 1.0) + 1.0) * (m / 2))
    return jnp.clip(idx, 0, m - 1).astype(jnp.int32), scale


def dequantize(idx, scale, bits):
    m = 2 ** bits
    return (-1.0 + (2.0 * idx.astype(F32) + 1.0) * (1.0 / m)) * scale


def pack(idx, bits):
    """int32 words; slot i of word w holds the code of entry i*W + w."""
    k = 32 // bits
    w = idx.shape[-1] // k
    words = idx[..., :w]
    for i in range(1, k):
        words = words | (idx[..., i * w:(i + 1) * w] << (i * bits))
    return words


def encode(chunks, signs, bits):
    """(words, scale) of rows of chunk entries: the wire payload."""
    idx, scale = quantize(fwht(chunks * signs), bits)
    return pack(idx, bits), scale


def roundtrip(u, signs, bits: int, chunk: int):
    """Decode(encode(u)) of one leaf, in the leaf's shape."""
    idx, scale = quantize(fwht(to_chunks(u, chunk) * signs), bits)
    dec = fwht(dequantize(idx, scale, bits)) * signs
    return dec.reshape(-1)[:u.size].reshape(u.shape)


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------
def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up to `lr` over `warmup` steps, then cosine to 0 at
    `total` (step counts from 1)."""
    peak, warm, total = opt["lr"], opt["warmup"], opt["total"]
    if step < warm:
        return peak * step / warm
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return 0.5 * peak * (1 + math.cos(math.pi * frac))


# ---------------------------------------------------------------------------
# One training step over m workers
# ---------------------------------------------------------------------------
class Trainer:
    """The reference's training step over m workers, worker w on device w.

    One program computes every worker's loss and gradient; the codec, the
    mean over workers and AdamW then run one leaf at a time, so that no
    more than one leaf's codec and optimizer work is in flight. State:
    (params, mu, nu, ef), dicts by leaf name; ef leaves are stacked over
    the workers, sharded one per device."""

    def __init__(self, cfg: dict, cell: dict, names: list[str], devices,
                 dtype=F32, precision: str | None = None):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import numpy as np
        self.cfg, self.cell, self.names = cfg, cell, list(names)
        self.m = cell["mesh"][0]
        self.mesh = Mesh(np.asarray(devices[:self.m]), ("w",))
        self.rep = NamedSharding(self.mesh, P())
        self.per_worker = NamedSharding(self.mesh, P("w"))
        self.compress = cell["strategy"] != "psum"
        self.use_ef = self.compress and cell["error_feedback"]
        self.precision = precision or ("highest" if dtype == F32
                                       else "default")
        bits, chunk = cell["bits"], cell["chunk"]
        names = self.names

        def grads(params, tokens):
            lval, g = jax.value_and_grad(
                lambda p: loss(cfg, p, tokens[0], dtype))(params)
            return lval[None], {n: g[n].astype(F32)[None] for n in names}

        self._grads = jax.jit(jax.shard_map(
            grads, mesh=self.mesh, in_specs=(P(), P("w")),
            out_specs=(P("w"), P("w")), check_vma=False))
        self._codec = jax.jit(jax.vmap(
            lambda u, signs: roundtrip(u, signs, bits, chunk),
            in_axes=(0, None)), out_shardings=self.per_worker)

        def mean(stacked):
            total = stacked[0]
            for w in range(1, stacked.shape[0]):
                total = total + stacked[w]
            return total / stacked.shape[0]

        self._mean = jax.jit(mean, out_shardings=self.rep)
        self._sq = jax.jit(lambda x: jnp.sum(jnp.square(x)))
        opt = cell["optimizer"]
        b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

        def adam(p, mu, nu, g, scale, lr, c1, c2):
            g = g * scale
            mu = b1 * mu + (1 - b1) * g
            nu = b2 * nu + (1 - b2) * jnp.square(g)
            p = p - lr * ((mu / c1) / (jnp.sqrt(nu / c2) + eps) + wd * p)
            return p, mu, nu, jnp.sqrt(jnp.sum(jnp.square(g)))

        self._adam = jax.jit(adam, donate_argnums=(0, 1, 2))
        self._signs = {n: frame_signs(i, chunk) for i, n in enumerate(names)}

    def init_state(self, params: dict):
        """State from the initial weights (copied, so `params` survive)."""
        zeros = lambda n: jax.device_put(jnp.zeros(params[n].shape, F32),
                                         self.rep)
        ef = {n: jax.device_put(
            jnp.zeros((self.m,) + (params[n].shape if self.use_ef else (1,)),
                      F32), self.per_worker) for n in self.names}
        return ({n: jnp.copy(params[n]) for n in self.names},
                {n: zeros(n) for n in self.names},
                {n: zeros(n) for n in self.names}, ef)

    def step(self, state, tokens, step: int, keep_grad: bool = False):
        """tokens (m, B, S+1) -> (state, readings): the mean loss, and per
        leaf the norm of the gradient handed to AdamW and of the new error
        feedback over all workers; with `keep_grad`, also that gradient's
        leaves, copied to the host (`g`)."""
        params, mu, nu, ef = state
        with jax.default_matmul_precision(self.precision):
            lvals, g = self._grads(params, jax.device_put(tokens,
                                                          self.per_worker))
        mean, ef_norm = {}, []
        for n in self.names:
            u = g.pop(n)
            if self.compress:
                if self.use_ef:
                    u = u + ef[n]
                dec = self._codec(u, self._signs[n])
                if self.use_ef:
                    ef[n] = u - dec
                    ef_norm.append(float(jnp.sqrt(self._sq(ef[n]))))
                u = dec
            mean[n] = self._mean(u)
            del u
        gnorm = math.sqrt(sum(float(self._sq(mean[n])) for n in self.names))
        opt = self.cell["optimizer"]
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-12))
        lr = learning_rate(opt, step)
        c1, c2 = 1 - opt["b1"] ** step, 1 - opt["b2"] ** step
        grad_norm, kept = [], []
        for n in self.names:
            g = mean.pop(n)
            if keep_grad:
                kept.append(np.asarray(g * scale))
            params[n], mu[n], nu[n], gn = self._adam(
                params[n], mu[n], nu[n], g, scale, lr, c1, c2)
            del g
            grad_norm.append(float(gn))
        readings = {"loss": float(jnp.mean(lvals)),
                    "grad": jnp.asarray(grad_norm),
                    "ef": jnp.asarray(ef_norm if self.use_ef
                                      else [0.0] * len(self.names))}
        if keep_grad:
            readings["g"] = kept
        return (params, mu, nu, ef), readings


def update_norms(params: dict, params0: dict, names):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(params[n] - params0[n])))
                      for n in names])
