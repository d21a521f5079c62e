"""Plain float32 reference for a served DiT under UniPC sampling.

Independent of the system under test: it imports only jax and numpy, and
follows the published descriptions, not the program's code.

- Denoiser: DiT with adaLN-zero blocks (Peebles & Xie 2023, arXiv:2212.09748).
  Every matmul runs at HIGHEST precision (true float32 on a TPU), attention
  softmax and LayerNorm in float32. Departures from the paper, all taken from
  the configuration as the repository states it: inputs are pre-patchified
  tokens (a linear patch projection, no positional embedding, no biases on
  the dense layers), LayerNorm eps 1e-5 (the paper's code uses 1e-6), GELU in
  its tanh form, timestep features scaled by 1000 before the sinusoids.
- Guidance: classifier-free guidance, eps = (1 + g) eps_cond - g eps_null
  (Ho & Salimans 2022), with a per-request g.
- Sampler: multistep UniPC-p with the B2(h) variant in data prediction
  (Zhao et al. 2023, arXiv:2302.04867, Algorithms 5-8), written after the
  paper's reference implementation: the corrector UniC at every step but the
  last, warm-up orders min(p, i) and lower orders on the final steps, the
  single-point systems fixed at 0.5 (App. F). Grid uniform in half log-SNR on
  the VP linear-beta schedule. All solver coefficients in float64 on the host.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# weights: names and shapes of the denoiser's parameters
# ---------------------------------------------------------------------------

def param_specs(model: dict) -> dict:
    """{path: (shape, std)} for every weight of the denoiser, in a nested
    layout the weight generator fills from the seed. `std` is the normal's
    scale; dense layers get 1/sqrt(fan_in), the adaLN projections (zero at a
    fresh init, which would make the network output exactly zero) a gain of
    their own."""
    d, f, L = model["d_model"], model["d_ff"], model["latent_dim"]
    n, hd, H = model["num_layers"], model["head_dim"], model["num_heads"]
    w = model["weights"]
    ada = w["ada_gain"] / math.sqrt(d)
    fan = lambda k: 1.0 / math.sqrt(k)
    return {
        "backbone/in_proj": ((L, d), fan(L)),
        "backbone/t_mlp1": ((model["time_features"], d),
                            fan(model["time_features"])),
        "backbone/t_mlp2": ((d, d), fan(d)),
        "backbone/class_embed": ((model["num_classes"] + 1, d),
                                 w["class_std"]),
        "backbone/blocks/attn/wq": ((n, d, H * hd), fan(d)),
        "backbone/blocks/attn/wk": ((n, d, H * hd), fan(d)),
        "backbone/blocks/attn/wv": ((n, d, H * hd), fan(d)),
        "backbone/blocks/attn/wo": ((n, H * hd, d), fan(H * hd)),
        "backbone/blocks/w1": ((n, d, f), fan(d)),
        "backbone/blocks/w2": ((n, f, d), fan(f)),
        "backbone/blocks/ada": ((n, d, 6 * d), ada),
        "backbone/blocks/ada_b": ((n, 6 * d), w["bias_std"]),
        "backbone/final_ada": ((d, 2 * d), ada),
        "backbone/final_ada_b": ((2 * d,), w["bias_std"]),
        "backbone/out_proj": ((d, L), fan(d)),
    }


# ---------------------------------------------------------------------------
# denoiser
# ---------------------------------------------------------------------------

def round_mantissa(x, bits):
    """x rounded to `bits` explicit mantissa bits (the exponent range kept):
    7 is bfloat16's precision, 3 float8 e4m3's. None leaves x as it is."""
    if bits is None:
        return x
    m, e = jnp.frexp(x)
    scale = 2.0 ** (bits + 1)
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def _mm(x, w, bits=None):
    q = partial(round_mantissa, bits=bits)
    return jnp.einsum("...k,kn->...n", q(x), q(w), precision=HI)


def _modulate(x, shift, scale, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * (1.0 + scale[:, None]) + shift[:, None]


def _attention(x, p, heads, head_dim, bits=None):
    B, T, _ = x.shape
    r = partial(round_mantissa, bits=bits)
    q = r(_mm(x, p["wq"], bits)).reshape(B, T, heads, head_dim)
    k = r(_mm(x, p["wk"], bits)).reshape(B, T, heads, head_dim)
    v = r(_mm(x, p["wv"], bits)).reshape(B, T, heads, head_dim)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(head_dim)
    a = r(jax.nn.softmax(s, axis=-1))
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HI)
    return _mm(o.reshape(B, T, heads * head_dim), p["wo"], bits)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


@partial(jax.jit, static_argnames=("heads", "head_dim", "eps", "bits"))
def dit_eps(params, x, tfeat, class_ids, *, heads, head_dim, eps, bits=None):
    """eps-hat for latents x (B, T, L) at one timestep. `tfeat` (256,) are
    the sinusoidal timestep features (computed on the host in float64).

    `bits` computes the same network at a lower precision: every matmul
    operand, the conditioning vector, the attention probabilities, the
    modulated activations and the residual stream rounded to that many
    mantissa bits (accumulation stays float32). That is the control the
    benchmark's comparison has to fail; None is the reference itself."""
    p = params["backbone"]
    r = partial(round_mantissa, bits=bits)
    B = x.shape[0]
    h = r(_mm(x, p["in_proj"], bits))
    c = _mm(jax.nn.silu(_mm(tfeat[None], p["t_mlp1"])), p["t_mlp2"])
    c = r(jax.nn.silu(jnp.broadcast_to(c, (B, c.shape[-1]))
                      + p["class_embed"][class_ids]))

    def block(h, bp):
        mod = r(_mm(c, bp["ada"], bits) + bp["ada_b"])
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
        a = _attention(r(_modulate(h, sh1, sc1, eps)), bp["attn"], heads,
                       head_dim, bits)
        h = r(h + g1[:, None] * a)
        y = _mm(r(_gelu_tanh(_mm(r(_modulate(h, sh2, sc2, eps)), bp["w1"],
                                 bits))), bp["w2"], bits)
        return r(h + g2[:, None] * y), None

    h, _ = jax.lax.scan(block, h, p["blocks"])
    sh, sc = jnp.split(r(_mm(c, p["final_ada"], bits) + p["final_ada_b"]), 2,
                       axis=-1)
    return _mm(r(_modulate(h, sh, sc, eps)), p["out_proj"], bits)


def time_features(t: float, dim: int = 256, max_period: float = 10000.0):
    half = dim // 2
    freqs = np.exp(-math.log(max_period) * np.arange(half) / half)
    ang = 1000.0 * t * freqs
    return np.concatenate([np.cos(ang), np.sin(ang)]).astype(np.float32)


# ---------------------------------------------------------------------------
# UniPC
# ---------------------------------------------------------------------------

class VPLinear:
    """alpha_t = exp(-t^2 (b1 - b0) / 4 - t b0 / 2), sigma^2 = 1 - alpha^2."""

    def __init__(self, beta_0, beta_1, T, t_eps):
        self.b0, self.b1, self.T, self.t_eps = beta_0, beta_1, T, t_eps

    def log_alpha(self, t):
        return -0.25 * t ** 2 * (self.b1 - self.b0) - 0.5 * t * self.b0

    def alpha(self, t):
        return np.exp(self.log_alpha(t))

    def sigma(self, t):
        return np.sqrt(1.0 - np.exp(2.0 * self.log_alpha(t)))

    def lam(self, t):
        return np.log(self.alpha(t)) - np.log(self.sigma(t))

    def t_of_lam(self, lam):
        log_a2 = -np.logaddexp(0.0, -2.0 * lam)
        d = self.b1 - self.b0
        return (-self.b0 + np.sqrt(self.b0 ** 2 - 2.0 * d * log_a2)) / d


def unipc_plan(schedule: dict, solver: dict):
    """Per-step coefficients of multistep UniPC (data prediction, B2(h)).

    Returns (ts, steps): ts the M+1 grid times from T down to t_eps; steps a
    list over i = 1..M of dicts with the transfer weights on x and on the
    newest data prediction m0, the predictor's and the corrector's weights on
    the differences (m_k - m0) for k = 1..p-1 (newest first), the
    corrector's weight on (m_t - m0), and whether the corrector runs."""
    assert solver["prediction"] == "data" and solver["variant"] == "bh2"
    assert solver["spacing"] == "logsnr"
    ns = VPLinear(schedule["beta_0"], schedule["beta_1"], schedule["T"],
                  schedule["t_eps"])
    M, order = solver["nfe"], solver["order"]
    lams = np.linspace(ns.lam(ns.T), ns.lam(ns.t_eps), M + 1)
    ts = ns.t_of_lam(lams)
    lams, alphas, sigmas = ns.lam(ts), ns.alpha(ts), ns.sigma(ts)
    steps = []
    for i in range(1, M + 1):
        p = min(order, i)
        if solver["lower_order_final"]:
            p = min(p, M + 1 - i)
        h = lams[i] - lams[i - 1]
        rks = [(lams[i - 1 - k] - lams[i - 1]) / h for k in range(1, p)]
        rks.append(1.0)
        hh = -h                         # data prediction works in -h
        h_phi_1 = math.expm1(hh)
        h_phi_k = h_phi_1 / hh - 1.0
        B_h = math.expm1(hh)            # B2(h)
        R, b, fact = [], [], 1
        for n in range(1, p + 1):
            R.append(np.asarray(rks) ** (n - 1))
            b.append(h_phi_k * fact / B_h)
            fact *= n + 1
            h_phi_k = h_phi_k / hh - 1.0 / fact
        R, b = np.stack(R), np.asarray(b)
        if p == 1:
            rho_p = np.zeros(0)
        elif p == 2:
            rho_p = np.array([0.5])
        else:
            rho_p = np.linalg.solve(R[:-1, :-1], b[:-1])
        rho_c = np.array([0.5]) if p == 1 else np.linalg.solve(R, b)
        a_t = alphas[i]
        # x_t = x_t_ - a_t B_h sum_k rho_k D1_k with D1_k = (m_k - m0) / r_k
        scale = -a_t * B_h
        steps.append({
            "x": sigmas[i] / sigmas[i - 1],
            "m0": -a_t * h_phi_1,
            "pred": [scale * r / rk for r, rk in zip(rho_p, rks[:-1])],
            "corr": [scale * r / rk for r, rk in zip(rho_c[:-1], rks[:-1])],
            "corr_new": scale * rho_c[-1],
            "use_corrector": (i < M) or solver["corrector_at_last"],
        })
    return ts, alphas, sigmas, steps


def sample(params, model: dict, schedule: dict, solver: dict, x_T, class_ids,
           g=None, null_class: int | None = None, bits=None):
    """x_0 from x_T (B, T, L) for B requests. `class_ids` (B,) int; with
    `g` (B,) the eval is guided against `null_class`. `bits` runs the
    denoiser at a lower precision (see `dit_eps`); the sampler's state and
    combine stay float32."""
    ts, alphas, sigmas, steps = unipc_plan(schedule, solver)
    kw = dict(heads=model["num_heads"], head_dim=model["head_dim"],
              eps=model["norm_eps"], bits=bits)
    B = x_T.shape[0]
    ids = jnp.asarray(class_ids, jnp.int32)
    if g is not None:
        ids = jnp.concatenate([ids, jnp.full((B,), null_class, jnp.int32)])
        gg = jnp.asarray(g, jnp.float32)[:, None, None]

    def data_pred(x, i):
        tf = jnp.asarray(time_features(float(ts[i]), model["time_features"]))
        if g is None:
            e = dit_eps(params, x, tf, ids, **kw)
        else:
            ee = dit_eps(params, jnp.concatenate([x, x]), tf, ids, **kw)
            e = (1.0 + gg) * ee[:B] - gg * ee[B:]
        return (x - np.float32(sigmas[i]) * e) / np.float32(alphas[i])

    x = jnp.asarray(x_T, jnp.float32)
    hist = [data_pred(x, 0)]            # newest first
    for i, st in enumerate(steps, start=1):
        m0 = hist[0]
        base = np.float32(st["x"]) * x + np.float32(st["m0"]) * m0
        x_pred = base
        for w, m in zip(st["pred"], hist[1:]):
            x_pred = x_pred + np.float32(w) * (m - m0)
        if not st["use_corrector"]:
            x = x_pred
            break
        m_t = data_pred(x_pred, i)
        x = base + np.float32(st["corr_new"]) * (m_t - m0)
        for w, m in zip(st["corr"], hist[1:]):
            x = x + np.float32(w) * (m - m0)
        hist = [m_t] + hist[: solver["order"] - 1]
    return x
