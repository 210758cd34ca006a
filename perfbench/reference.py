"""Plain reference of a ``dense_lm`` configuration with a spectral MLP:
weights from the seed, forward, loss, gradients and the SCT optimizer
step, in ``jax.numpy`` at float32 with every matmul at ``highest``
precision. No cache, no kernels, no batching tricks. It imports nothing
of the program and takes nothing the program made.

What it follows, from the configuration file and the paper:

* weights: the same draws as the program's initialisation from the same
  seed (embedding N(0, 1/d); dense projections N(0, 1/fan_in); spectral
  factors U, V orthonormal by QR of Gaussians, s geometric from 1 to
  1e-2 scaled to the Frobenius norm of a fan-in dense init; RMSNorm
  scales 1). The key splits mirror the program's so the weights agree.
* block: pre-RMSNorm attention with rotary embeddings (rotate-half
  form, ``rope_theta``), grouped KV heads, causal softmax at
  1/sqrt(head_dim); pre-RMSNorm SwiGLU MLP whose three projections are
  ``((x U) * s) V^T``; tied embedding as LM head.
* loss: mean token cross-entropy plus ``z_loss * mean(logsumexp^2)``.
* step: global-norm clip, AdamW with bias correction (no weight decay
  on spectral factors), linear warmup then cosine to 10% of the peak,
  then every U and V replaced by the Q of its QR factorisation with
  the signs that make R's diagonal positive (paper Algorithm 1). A
  configuration that names ``cholesky_qr2`` is compared against this
  same QR retraction, which Cholesky-QR2 equals in exact arithmetic.

Departures: none in the mathematics. It runs in blocks so that it fits
one chip at published widths: gradients one batch row at a time, with
each layer recomputed in the backward pass.

``lowp="fp8"`` rounds both operands of every matmul to float8 (e4m3
forward, e5m2 for the incoming gradient, per-tensor scale) and serves
as the control: the precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also those past 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


# ------------------------------------------------------------ matmuls --

def _round(x, dtype, top):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _q_fwd(x):
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _q_bwd(x):
    return _round(x, jnp.float8_e5m2, 57344.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return jnp.einsum(spec, _q_fwd(a), _q_fwd(b), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    return _fp8_einsum(spec, a, b), (a, b)


def _fp8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     _q_fwd(a), _q_fwd(b))
    return vjp(_q_bwd(g))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(spec, a, b, lowp):
    if lowp is None:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if lowp == "fp8":
        return _fp8_einsum(spec, a, b)
    raise ValueError(f"unknown lowp {lowp!r}")


# ------------------------------------------------------------ weights --

def _dims(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h,
            cfg["intermediate_size"], cfg["vocab_size"])


def _spectral(key, m, n, k):
    ku, kv = jax.random.split(key)
    U, _ = jnp.linalg.qr(jax.random.normal(ku, (m, k), jnp.float32))
    V, _ = jnp.linalg.qr(jax.random.normal(kv, (n, k), jnp.float32))
    decay = jnp.logspace(0.0, -2.0, k)
    s = decay * ((1.0 / math.sqrt(m)) * math.sqrt(m * n) / jnp.linalg.norm(decay))
    return {"U": U, "s": s.astype(jnp.float32), "V": V}


def _dense(key, m, n):
    return {"w": jax.random.normal(key, (m, n), jnp.float32) * m ** -0.5}


def init_params(cfg: dict, key):
    """fp32 weights of the configuration from ``key`` (``seed_key`` of
    the seed; jit this with the key as an argument, so that one program
    serves every seed)."""
    d, h, kvh, hd, ff, V = _dims(cfg)
    k = cfg["sct"]["rank"]
    ke, kl, _, _ = jax.random.split(key, 4)

    def layer(key):
        k1, k2 = jax.random.split(key)
        kq, kk, kv, ko = jax.random.split(k1, 4)
        ks = jax.random.split(k2, 3)
        return {
            "attn_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {"wq": _dense(kq, d, h * hd), "wk": _dense(kk, d, kvh * hd),
                     "wv": _dense(kv, d, kvh * hd), "wo": _dense(ko, h * hd, d)},
            "mlp_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "mlp": {"up": _spectral(ks[0], d, ff, k), "down": _spectral(ks[1], ff, d, k),
                    "gate": _spectral(ks[2], d, ff, k)},
        }

    return {
        "embed": {"w": jax.random.normal(ke, (V, d), jnp.float32) * d ** -0.5},
        "layers": jax.vmap(layer)(jax.random.split(kl, cfg["num_hidden_layers"])),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
    }


# ------------------------------------------------------------ forward --

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs               # (s, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _spectral_apply(p, x, lowp):
    y = _mm("sm,mk->sk", x, p["U"], lowp) * p["s"]
    return _mm("sk,nk->sn", y, p["V"], lowp)


def _layer(cfg, p, x, lowp):
    """One block over one sequence x (s, d)."""
    d, h, kvh, hd, _, _ = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    s = x.shape[0]
    pos = jnp.arange(s)
    a = _rms(x, p["attn_norm"]["scale"], eps)
    q = _mm("sd,dn->sn", a, p["attn"]["wq"]["w"], lowp).reshape(s, h, hd)
    k = _mm("sd,dn->sn", a, p["attn"]["wk"]["w"], lowp).reshape(s, kvh, hd)
    v = _mm("sd,dn->sn", a, p["attn"]["wv"]["w"], lowp).reshape(s, kvh, hd)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=1)                  # head j reads kv head j // rep
    v = jnp.repeat(v, rep, axis=1)
    scores = _mm("qhd,khd->hqk", q, k, lowp) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("hqk,khd->qhd", probs, v, lowp).reshape(s, h * hd)
    x = x + _mm("sn,nd->sd", o, p["attn"]["wo"]["w"], lowp)
    m = _rms(x, p["mlp_norm"]["scale"], eps)
    up = _spectral_apply(p["mlp"]["up"], m, lowp)
    gate = _spectral_apply(p["mlp"]["gate"], m, lowp)
    return x + _spectral_apply(p["mlp"]["down"], jax.nn.silu(gate) * up, lowp)


def logits(cfg, params, tokens, lowp=None, remat=False):
    """Logits (s, vocab) of one sequence of token ids (s,)."""
    x = params["embed"]["w"][tokens]
    body = lambda x, p: (_layer(cfg, p, x, lowp), None)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return _mm("sd,vd->sv", x, params["embed"]["w"], lowp)


def row_loss(cfg, params, tokens, labels, lowp=None):
    lg = logits(cfg, params, tokens, lowp, remat=True)
    lse = jax.nn.logsumexp(lg, axis=-1)
    nll = lse - jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll) + cfg["optimizer"]["z_loss"] * jnp.mean(lse * lse)


# --------------------------------------------------------------- step --

def lr_at(opt: dict, step: int) -> float:
    """Learning rate of 0-based ``step``: linear warmup to the peak, then
    cosine decay to a tenth of it at ``total_steps``."""
    t = step + 1.0
    if t < opt["warmup"]:
        return opt["lr"] * min(t / max(opt["warmup"], 1), 1.0)
    frac = min(max((t - opt["warmup"]) / max(opt["total_steps"] - opt["warmup"], 1), 0.0), 1.0)
    return opt["lr"] * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * frac)))


def _qr_retract(u):
    q, r = jnp.linalg.qr(u)
    d = jnp.diagonal(r, axis1=-2, axis2=-1)
    return q * jnp.where(d >= 0, 1.0, -1.0)[..., None, :]


def _adamw_leaf(p, g, m, v, lr, b1c, b2c, wd, *, b1, b2, eps):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    upd = (m / b1c) / (jnp.sqrt(v / b2c) + eps)
    return p - lr * (upd + wd * p), m, v


def leaf_norms(tree) -> dict:
    """{path: fp32 norm} of every leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


def _retract_all(params):
    mlp = {name: dict(g, U=_qr_retract(g["U"]), V=_qr_retract(g["V"]))
           for name, g in params["layers"]["mlp"].items()}
    return dict(params, layers=dict(params["layers"], mlp=mlp))


class Trainer:
    """The reference's training run: ``run(batches)`` takes the checked
    steps' batches and returns what the check compares: each step's
    loss, the norm of every leaf of the first (clipped) gradient, and
    of every leaf's change over all the steps. ``rows`` keeps only each
    batch's first rows (a planted fault: part of the batch left out,
    the mean taken over the rest).

    Memory: the device holds the weights, the running gradient and one
    row's gradient program (one layer's activations recomputed at a
    time); the Adam moments stay beside them where they fit, and else
    live on the host and board the device one leaf at a time."""

    def __init__(self, cfg: dict, seed: int, lowp=None, rows=None):
        self.cfg, self.seed, self.lowp, self.rows = cfg, seed, lowp, rows
        opt = cfg["optimizer"]
        with jax.default_matmul_precision("highest"):
            self._grad = jax.jit(jax.value_and_grad(
                lambda p, t, l: row_loss(cfg, p, t, l, lowp)))
            self._acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                                donate_argnums=(0,))
            self._scale = jax.jit(lambda g, c: jax.tree.map(lambda x: x * c, g),
                                  donate_argnums=(0,))
            self._sumsq = jax.jit(lambda g: sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            self._adamw = jax.jit(functools.partial(
                _adamw_leaf, b1=opt["b1"], b2=opt["b2"], eps=opt["eps"]),
                donate_argnums=(0, 2, 3))
            self._retract = jax.jit(_retract_all, donate_argnums=(0,))
            self._norms = jax.jit(leaf_norms)
            self._diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))

    def _grads(self, params, tokens, labels):
        n = tokens.shape[0] if self.rows is None else self.rows
        total, acc = 0.0, None
        for r in range(n):
            loss, g = self._grad(params, jnp.asarray(tokens[r]), jnp.asarray(labels[r]))
            total += float(loss)
            acc = g if acc is None else self._acc(acc, g)
        return total / n, self._scale(acc, jnp.float32(1.0 / n))

    def _step(self, params, grads, moments, step):
        opt = self.cfg["optimizer"]
        gnorm = float(jnp.sqrt(self._sumsq(grads)))
        grads = self._scale(grads, jnp.float32(min(1.0, opt["clip_norm"] / (gnorm + 1e-12))))
        count = step + 1
        scal = [jnp.float32(x) for x in (lr_at(opt, step), 1.0 - opt["b1"] ** count,
                                         1.0 - opt["b2"] ** count)]
        flat_p, tdef = jax.tree_util.tree_flatten_with_path(params)
        flat_g = jax.tree.leaves(grads)
        new = []
        for i, ((path, p), g) in enumerate(zip(flat_p, flat_g)):
            spectral = "['mlp']" in jax.tree_util.keystr(path)
            wd = jnp.float32(0.0 if spectral else opt["weight_decay"])
            m, v = moments[i] if moments[i] is not None else (
                jnp.zeros_like(p), jnp.zeros_like(p))
            p, m, v = self._adamw(p, g, jnp.asarray(m), jnp.asarray(v), *scal, wd)
            moments[i] = (m, v) if self.moments_on_device else (np.asarray(m), np.asarray(v))
            new.append(p)
        return self._retract(jax.tree.unflatten(tdef, new)), grads

    def _moments_fit(self, params, tokens, labels) -> bool:
        """Whether Adam's two moments fit on the device beside the
        weights, the running gradient and one row's gradient program."""
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        if not limit:
            return False
        m = self._grad.lower(params, jnp.asarray(tokens[0]),
                             jnp.asarray(labels[0])).compile().memory_analysis()
        weights = sum(x.nbytes for x in jax.tree.leaves(params))
        return 4 * weights + m.output_size_in_bytes + m.temp_size_in_bytes < 0.9 * limit

    def run(self, batches) -> dict:
        import time

        tick = time.perf_counter()
        self.seconds = {"init": 0.0, "grads": 0.0, "update": 0.0, "change": 0.0}

        def lap(what):
            nonlocal tick
            now = time.perf_counter()
            self.seconds[what] += now - tick
            tick = now

        with jax.default_matmul_precision("highest"):
            params = jax.jit(functools.partial(init_params, self.cfg))(seed_key(self.seed))
            p0 = jax.device_get(params)
            moments = [None] * len(jax.tree.leaves(params))
            self.moments_on_device = self._moments_fit(params, *batches[0])
            losses, first_grad = [], None
            lap("init")
            for step, (tokens, labels) in enumerate(batches):
                loss, grads = self._grads(params, tokens, labels)
                losses.append(loss)
                lap("grads")
                params, grads = self._step(params, grads, moments, step)
                jax.block_until_ready(params)
                lap("update")
                if first_grad is None:
                    first_grad = {k: float(v) for k, v in self._norms(grads).items()}
                del grads
            del moments
            flat, _ = jax.tree_util.tree_flatten_with_path(params)
            change = {jax.tree_util.keystr(p): float(self._diff(x, jnp.asarray(x0)))
                      for (p, x), x0 in zip(flat, jax.tree.leaves(p0))}
            lap("change")
        return {"losses": losses, "grad_norms": first_grad, "change_norms": change}


# ------------------------------------------------------------ serving --

class Scorer:
    """Teacher-forced logits of served sequences, one at a time, padded
    to one length so a single program serves every request."""

    def __init__(self, cfg: dict, seed: int, pad_to: int):
        self.cfg, self.pad_to = cfg, pad_to
        with jax.default_matmul_precision("highest"):
            self.params = jax.jit(functools.partial(init_params, cfg))(seed_key(seed))
            self._fns = {lp: jax.jit(functools.partial(self._score, lowp=lp))
                         for lp in (None, "fp8")}

    def _score(self, params, tokens, lowp):
        with jax.default_matmul_precision("highest"):
            lg = logits(self.cfg, params, tokens, lowp)
        return jnp.max(lg, axis=-1), lg, jnp.argmax(lg, axis=-1)

    def gaps(self, prompt, served, control: bool = False) -> np.ndarray:
        """For each served token: how far its reference logit lies below
        the reference's best at that position. With ``control``, the
        token judged is the one the fp8 control puts first there."""
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n = len(seq)
        if n > self.pad_to:
            raise ValueError(f"sequence of {n} tokens exceeds pad_to={self.pad_to}")
        padded = np.zeros(self.pad_to, np.int32)
        padded[:n] = seq
        best, lg, _ = self._fns[None](self.params, jnp.asarray(padded))
        at = np.arange(len(prompt) - 1, n)
        if control:
            _, _, pick = self._fns["fp8"](self.params, jnp.asarray(padded))
            judged = np.asarray(pick)[at]
        else:
            judged = np.asarray(served, np.int32)
        lg_at = np.asarray(lg[jnp.asarray(at), jnp.asarray(judged)])
        return np.asarray(best)[at] - lg_at
