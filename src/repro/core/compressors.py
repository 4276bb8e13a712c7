"""Compression operators and the C(eta, omega) calculus (Ch. 2, EF-BV).

The dissertation's unified compressor class C(eta, omega) bounds
  (i)  || E[C(x)] - x ||        <= eta   ||x||      (relative bias)
  (ii) E|| C(x) - E[C(x)] ||^2  <= omega ||x||^2    (relative variance)

Implemented operators (all shape-preserving, "value-sparse"):
  * identity
  * rand-k           — unbiased sparsifier, U(omega) with omega = d/k - 1
  * top-k            — biased contractive, B(alpha) with alpha = k/d
                       (=> C(eta, 0) with eta = sqrt(1 - k/d))
  * block top-k      — top-k within fixed blocks (TPU-friendly); contractive
                       with alpha >= k/d (equality when energy is uniform)
  * qsgd (s-level)   — stochastic-rounding quantizer, unbiased; blockwise
                       absmax scaling; omega estimated empirically (the
                       classical bound min(d/s^2, sqrt(d)/s) applies to
                       2-norm scaling over the full vector)
  * mix-(k,k')       — mixture: top-k with prob rho else rand-k' (App. A.1.1)
  * comp-(k,k')      — composition: top-k applied to rand-k' output (A.1.2)
  * scale(C, lam)    — lam*C; Prop 2.2.1: eta' = lam*eta + 1 - lam,
                       omega' = lam^2 * omega

The optimal scalings of Prop 2.2.2 / Sect. 2.4:
  lambda* = min((1-eta) / ((1-eta)^2 + omega),     1)
  nu*     = min((1-eta) / ((1-eta)^2 + omega_ran), 1)
with omega_ran = omega/n for n independent compressors (Sect. 2.2.2).

Every operator also reports ``payload_bits(d)`` — the bits a real system puts
on the wire — used by the EXPERIMENTS bit-accounting exactly as the paper
plots Fig 2.2 (bits per node vs suboptimality).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class WireSpec:
    """How a compressor's output is packed on the wire (repro.comm.codecs).

    scheme: dense | sparse_idx32 | sparse_block | sparse_bitmap | quant
    block/bits: quantizer blocking; axis: "flat" (blocks over the flattened
    tensor), "last" (blocks along the last dim, sharding-safe), or "kernel"
    (the Pallas quantize-pack layout).  ``gain`` is a post-scale applied by
    scale_compressor — the receiver multiplies it back in after dequant.
    """
    scheme: str = "dense"
    block: int = 0
    bits: int = 32
    axis: str = "flat"
    gain: float = 1.0


@dataclass(frozen=True)
class Compressor:
    name: str
    fn: Callable            # (key, flat_x) -> flat_x_hat
    eta: Optional[float]    # relative bias bound (None = unknown, estimate)
    omega: Optional[float]  # relative variance bound
    bits_per_dim: float     # payload bits per coordinate of the input
    deterministic: bool = False
    # sharding-safe operators handle any shape themselves: reshape(-1) of a
    # 2D-sharded leaf forces a GSPMD all-gather, so they must NOT flatten
    flatten: bool = True
    # wire format for repro.comm.codecs.encode/decode (None -> dense)
    wire: Optional[WireSpec] = None
    # fn runs a Pallas kernel, which GSPMD cannot partition: on a mesh each
    # device applies it to its own worker groups (distributed._map_groups)
    pallas: bool = False

    def __call__(self, key, x):
        if not self.flatten:
            return self.fn(key, x)
        shape = x.shape
        out = self.fn(key, x.reshape(-1))
        return out.reshape(shape)

    def payload_bits(self, d: int) -> float:
        return self.bits_per_dim * d

    def contractive_alpha(self) -> Optional[float]:
        """1 - (eta^2 + omega) when < 1 (Eq. 2.3); None otherwise."""
        if self.eta is None or self.omega is None:
            return None
        r = self.eta**2 + self.omega
        return (1.0 - r) if r < 1 else None


# ---------------------------------------------------------------------------
# Scaling calculus (Prop 2.2.1 / 2.2.2)
# ---------------------------------------------------------------------------
def scale_compressor(c: Compressor, lam: float) -> Compressor:
    eta = None if c.eta is None else lam * c.eta + (1.0 - lam)
    omega = None if c.omega is None else lam**2 * c.omega
    wire = c.wire
    if wire is not None:
        wire = replace(wire, gain=wire.gain * lam)
    return Compressor(
        name=f"scale({c.name},{lam:.4g})",
        fn=lambda key, x, c=c, lam=lam: lam * c.fn(key, x),
        eta=eta,
        omega=omega,
        bits_per_dim=c.bits_per_dim,
        deterministic=c.deterministic,
        # keep the flatten flag: dropping it silently re-enabled the
        # reshape(-1) that forces a GSPMD all-gather on sharded leaves
        flatten=c.flatten,
        wire=wire,
    )


def lambda_star(eta: float, omega: float) -> float:
    return min((1.0 - eta) / ((1.0 - eta) ** 2 + omega), 1.0)


def nu_star(eta: float, omega_ran: float) -> float:
    return min((1.0 - eta) / ((1.0 - eta) ** 2 + omega_ran), 1.0)


def omega_ran_independent(omega: float, n: int) -> float:
    """Independent randomness across n workers: omega_ran = omega / n."""
    return omega / n


def efbv_rates(eta: float, omega: float, omega_ran: float, lam: float, nu: float):
    """r, r_av, s*, theta* from Sect. 2.4 (used for stepsize selection)."""
    r = (1 - lam + lam * eta) ** 2 + lam**2 * omega
    r_av = (1 - nu + nu * eta) ** 2 + nu**2 * omega_ran
    s_star = math.sqrt((1 + r) / (2 * r)) - 1
    theta_star = s_star * (1 + s_star) * r / max(r_av, 1e-30)
    return r, r_av, s_star, theta_star


def efbv_stepsize(L: float, L_tilde: float, eta: float, omega: float,
                  omega_ran: float, lam: float, nu: float) -> float:
    """Upper bound of Thm 2.4.1: gamma <= 1 / (L + L~ sqrt(r_av/r)/s*)."""
    r, r_av, s_star, _ = efbv_rates(eta, omega, omega_ran, lam, nu)
    if r >= 1 or s_star <= 0:
        return 1.0 / (2 * L)
    return 1.0 / (L + L_tilde * math.sqrt(r_av / r) / s_star)


# ---------------------------------------------------------------------------
# Operators
# ---------------------------------------------------------------------------
def identity() -> Compressor:
    return Compressor("identity", lambda key, x: x, eta=0.0, omega=0.0,
                      bits_per_dim=32.0, deterministic=True,
                      wire=WireSpec("dense"))


def rand_k(k_frac: float) -> Compressor:
    """Keep a uniformly random floor(k_frac*d) coordinates scaled by d/k."""

    def fn(key, x):
        d = x.shape[0]
        k = max(1, int(round(k_frac * d)))
        scores = jax.random.uniform(key, (d,))
        thresh = -jax.lax.top_k(-scores, k)[0][-1]  # k-th smallest
        mask = (scores <= thresh).astype(x.dtype)
        return x * mask * (d / k)

    omega = 1.0 / k_frac - 1.0
    return Compressor(f"rand_k({k_frac:g})", fn, eta=0.0, omega=omega,
                      bits_per_dim=k_frac * (32 + 32),
                      wire=WireSpec("sparse_idx32"))


def top_k(k_frac: float) -> Compressor:
    """Keep the floor(k_frac*d) largest-magnitude coordinates (global)."""

    def fn(key, x):
        d = x.shape[0]
        k = max(1, int(round(k_frac * d)))
        thresh = jax.lax.top_k(jnp.abs(x), k)[0][-1]
        mask = (jnp.abs(x) >= thresh).astype(x.dtype)
        return x * mask

    eta = math.sqrt(max(0.0, 1.0 - k_frac))
    return Compressor(f"top_k({k_frac:g})", fn, eta=eta, omega=0.0,
                      bits_per_dim=k_frac * (32 + 32), deterministic=True,
                      wire=WireSpec("sparse_idx32"))


def block_top_k(k_frac: float, block: int = 2048) -> Compressor:
    """Exact top-k within contiguous blocks — the TPU-friendly variant used by
    the compressed grad-sync (bounded VMEM working set, no global sort).
    Contractive with alpha >= k/d: within each block b,
    ||C(x_b)-x_b||^2 <= (1-k_b/|b|)||x_b||^2, and k_b/|b| = k_frac."""

    def fn(key, x):
        d = x.shape[0]
        nb = -(-d // block)
        pad = nb * block - d
        xp = jnp.pad(x, (0, pad)).reshape(nb, block)
        kb = max(1, int(round(k_frac * block)))
        thresh = jax.lax.top_k(jnp.abs(xp), kb)[0][:, -1:]
        mask = (jnp.abs(xp) >= thresh).astype(x.dtype)
        return (xp * mask).reshape(-1)[:d]

    eta = math.sqrt(max(0.0, 1.0 - k_frac))
    return Compressor(f"block_top_k({k_frac:g},{block})", fn, eta=eta, omega=0.0,
                      bits_per_dim=k_frac * (32 + math.log2(block)),
                      deterministic=True,
                      wire=WireSpec("sparse_block", block=block))


def qsgd(bits: int = 8, block: int = 2048, stochastic: bool = True) -> Compressor:
    """Blockwise absmax s-level quantizer; stochastic rounding => unbiased."""
    s = 2 ** (bits - 1) - 1

    def fn(key, x):
        d = x.shape[0]
        nb = -(-d // block)
        pad = nb * block - d
        xp = jnp.pad(x, (0, pad)).reshape(nb, block)
        scale = jnp.max(jnp.abs(xp), axis=1, keepdims=True) / s
        scale = jnp.where(scale == 0, 1.0, scale)
        y = xp / scale
        if stochastic:
            noise = jax.random.uniform(key, y.shape, minval=-0.5, maxval=0.5)
            q = jnp.round(y + noise)
        else:
            q = jnp.round(y)
        q = jnp.clip(q, -s, s)
        return (q * scale).reshape(-1)[:d]

    # blockwise absmax stochastic rounding: per-coordinate error <= scale/2,
    # so variance <= block * scale^2/4 / ||x_b||^2 <= block/(4 s^2) (worst case
    # one dominant coordinate).  We report that worst-case bound.
    omega = block / (4.0 * s * s)
    return Compressor(f"qsgd({bits}b,{block})", fn,
                      eta=0.0 if stochastic else None,
                      omega=omega if stochastic else None,
                      bits_per_dim=float(bits),
                      deterministic=not stochastic,
                      wire=WireSpec("quant", block=block, bits=bits, axis="flat"))


def mix_k(k_frac_top: float, k_frac_rand: float, rho: float = 0.5) -> Compressor:
    """mix-(k,k') (App. A.1.1): top-k with prob rho, rand-k' with prob 1-rho."""
    t = top_k(k_frac_top)
    r = rand_k(k_frac_rand)

    def fn(key, x):
        k1, k2, k3 = jax.random.split(key, 3)
        coin = jax.random.uniform(k1) < rho
        return jnp.where(coin, t.fn(k2, x), r.fn(k3, x))

    bits = rho * t.bits_per_dim + (1 - rho) * r.bits_per_dim
    return Compressor(f"mix({k_frac_top:g},{k_frac_rand:g},{rho:g})", fn,
                      eta=None, omega=None, bits_per_dim=bits,
                      wire=WireSpec("sparse_idx32"))


def comp_k(k_frac_top: float, k_frac_rand: float) -> Compressor:
    """comp-(k,k') (App. A.1.2): top-k applied to the output of rand-k'
    (random support of size k', then the k largest among it, unscaled)."""

    def fn(key, x):
        d = x.shape[0]
        kr = max(1, int(round(k_frac_rand * d)))
        kt = max(1, int(round(k_frac_top * d)))
        scores = jax.random.uniform(key, (d,))
        thresh_r = -jax.lax.top_k(-scores, kr)[0][-1]
        sel = scores <= thresh_r
        masked = jnp.where(sel, jnp.abs(x), -jnp.inf)
        thresh_t = jax.lax.top_k(masked, kt)[0][-1]
        mask = (masked >= thresh_t).astype(x.dtype)
        return x * mask

    return Compressor(f"comp({k_frac_top:g},{k_frac_rand:g})", fn,
                      eta=None, omega=None,
                      bits_per_dim=k_frac_top * (32 + 32),
                      wire=WireSpec("sparse_idx32"))


def qsgd_sharded(bits: int = 8, block: int = 256, stochastic: bool = True) -> Compressor:
    """Sharding-safe qsgd: blocks run along the LAST axis only, so a
    (data, model)-sharded parameter leaf is quantized without the
    reshape(-1) that would force GSPMD to all-gather it (measured 1.3 TB/chip
    of temp in the hier param sync before this).  Falls back to a per-leaf
    scalar scale when the last dim doesn't block evenly."""
    s = 2 ** (bits - 1) - 1

    def fn(key, x):
        last = x.shape[-1] if x.ndim else 1
        if x.ndim >= 1 and last % block == 0:
            shaped = x.reshape(x.shape[:-1] + (last // block, block))
            scale = jnp.max(jnp.abs(shaped), axis=-1, keepdims=True) / s
        else:
            shaped = x
            scale = jnp.max(jnp.abs(x)) / s
        scale = jnp.where(scale == 0, 1.0, scale)
        y = shaped / scale
        if stochastic:
            noise = jax.random.uniform(key, y.shape)
            q = jnp.floor(y + noise)
        else:
            q = jnp.round(y)
        q = jnp.clip(q, -s, s) * scale
        return q.reshape(x.shape)

    return Compressor(f"qsgd_sharded({bits}b,{block})", fn,
                      eta=0.0 if stochastic else None,
                      omega=block / (4.0 * s * s) if stochastic else None,
                      bits_per_dim=float(bits), flatten=False,
                      wire=WireSpec("quant", block=block, bits=bits, axis="last"))


def qsgd_kernel(bits: int = 8, interpret: Optional[bool] = None) -> Compressor:
    """qsgd backed by the fused Pallas quantize-dequantize kernel
    (``interpret=None``: compiled or interpreted per kernels/backend.py)."""
    from repro.kernels.ops import quantize_dequantize
    from repro.kernels.quant8 import QBLOCK

    s = 2 ** (bits - 1) - 1

    def fn(key, x):
        return quantize_dequantize(x, key, bits=bits, interpret=interpret)

    return Compressor(f"qsgd_kernel({bits}b)", fn, eta=0.0,
                      omega=QBLOCK / (4.0 * s * s), bits_per_dim=float(bits),
                      wire=WireSpec("quant", block=QBLOCK, bits=bits, axis="kernel"),
                      pallas=True)


_REGISTRY = {
    "identity": identity,
    "rand_k": rand_k,
    "top_k": top_k,
    "topk_block": block_top_k,
    "qsgd": qsgd,
    "qsgd_sharded": qsgd_sharded,
    "qsgd_kernel": qsgd_kernel,
    "mix_k": mix_k,
    "comp_k": comp_k,
}


def make_compressor(name: str, **kw) -> Compressor:
    if name not in _REGISTRY:
        raise KeyError(f"unknown compressor {name!r}; known {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kw)


# ---------------------------------------------------------------------------
# Empirical (eta, omega) estimation — used when closed forms are unknown
# (mix/comp) and to validate the closed forms property-style in tests.
# ---------------------------------------------------------------------------
def estimate_eta_omega(c: Compressor, key, dim: int, n_vectors: int = 16,
                       n_samples: int = 64) -> tuple:
    """Empirical sup over test vectors of relative bias / variance."""
    kv, ks = jax.random.split(key)
    xs = jax.random.normal(kv, (n_vectors, dim))
    # heavy-tailed probes stress top-k style operators
    xs = xs * jnp.exp(2.0 * jax.random.normal(jax.random.fold_in(kv, 1), (n_vectors, dim)))

    def one_vector(x, key):
        keys = jax.random.split(key, n_samples)
        ys = jax.vmap(lambda k: c(k, x))(keys)
        mean = jnp.mean(ys, axis=0)
        bias = jnp.linalg.norm(mean - x) / (jnp.linalg.norm(x) + 1e-12)
        var = jnp.mean(jnp.sum((ys - mean) ** 2, axis=-1)) / (jnp.sum(x**2) + 1e-12)
        return bias, var

    keys = jax.random.split(ks, n_vectors)
    biases, variances = jax.vmap(one_vector)(xs, keys)
    return float(jnp.max(biases)), float(jnp.max(variances))


# ---------------------------------------------------------------------------
# Pytree plumbing
# ---------------------------------------------------------------------------
def tree_compress(c: Compressor, key, tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = [c(k, leaf) for k, leaf in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)
