"""What the RMSNorm decoder families share (``models/lfm2.py``,
``models/sdar.py``, ``models/deepseek.py``, ``models/falcon_h1.py``,
``models/nemotron_h.py``): bias-free projections created in the model's
dtype, the pre-norm through the repo's kernel, rotary tables in the
rotate-half layout (plain or YaRN-scaled frequencies), the SwiGLU
feed-forward (with a model's gate and down multipliers, where it has
them) and the two-matrix relu^2 one, grouped-query attention with or
without a per-head RMS norm of q and k, a rotary embedding and a scale
on the keys, and the Mamba-2 state-space mixer (with a model's µP
multipliers, where it has them). Written once, on arrays; inference only
(no autograd tape)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import Tensor
from ..kernels.pallas_fused import fused_rms_norm, fused_rope
from ..ops.linalg import _mxu_precision

__all__ = ["GroupedQueryAttention", "Mamba2Mixer", "NormalDraw", "Relu2MLP",
           "SwiGLU", "created_in", "linear", "mm",
           "pre_norm", "rms_head", "rope_tables", "rotate_half_rope",
           "rotate_half_rope_mxu", "yarn_inv_freq", "yarn_mscale"]


def rms_head(x, weight, eps):
    """Per-head RMS norm of q / k (a head's lanes a row: plain XLA, the
    kernel's rows are whole hidden rows)."""
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps)
    return (h * weight.astype(jnp.float32)).astype(x.dtype)


def rope_tables(positions, head_dim: int, theta: float, inv_freq=None):
    """cos, sin ``[..., head_dim]`` f32 for integer ``positions``: the
    half tables repeated, the layout ``fused_rope`` (rotate-half)
    takes. ``inv_freq`` ``[head_dim / 2]`` replaces the plain
    ``theta^(-2i/d)`` frequencies (:func:`yarn_inv_freq`)."""
    inv = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                    / head_dim) if inv_freq is None else inv_freq
    ang = positions.astype(jnp.float32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def rotate_half_rope(x, cos, sin):
    """``x cos + rotate_half(x) sin`` over the last axis, in float32, as
    plain XLA (``fused_rope``'s mathematics for an ``x`` that is a lane
    slice of a wider projection, which the compiler fuses into it)."""
    h = x.astype(jnp.float32)
    half = h.shape[-1] // 2
    turned = jnp.concatenate([-h[..., half:], h[..., :half]], -1)
    return (h * cos + turned * sin).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 m ln(factor) + 1`` (1 where
    nothing is scaled)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float = 32,
                  beta_slow: float = 1):
    """YaRN's ``[dim / 2]`` rotary frequencies (Peng et al. 2023, as
    the DeepSeek-V2 publication applies them): the fast dimensions keep
    ``f_i = theta^(-2i/dim)``, the slow ones are interpolated to ``f_i /
    factor``, and a linear ramp blends the two between the dimensions
    that turn ``beta_fast`` and ``beta_slow`` times over the original
    context."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_at(rotations):
        return dim * math.log(original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(f / factor * ramp + f * (1 - ramp), jnp.float32)


def mm(x, linear_layer):
    w = linear_layer.weight._data
    return jnp.dot(x, w, precision=_mxu_precision(x, w))


def created_in(param, dtype):
    """A parameter a stock layer made in the default dtype, in ``dtype``.
    The host waits for the cast: the float32 draft is freed only when it
    has run, and a model's drafts enqueued ahead of the device stand
    beside each other (3.5 GB of them after the last layer of an 8.79
    GB model on a warm compile cache: my chip run, PR 39)."""
    if dtype is not None and str(param._data.dtype) != dtype:
        param._replace_data(param._data.astype(dtype))
        param._data.block_until_ready()


class NormalDraw(nn.initializer.Initializer):
    """``nn.initializer.Normal`` for a table that fills a chip: N(mean,
    std) drawn in ONE compiled call. The stock initializer is three
    eager operations (the draw, ``std *``, ``mean +``) whose results
    stand beside each other when the host enqueues them ahead of the
    device: three float32 copies of a 261,120 x 5,120 table are 16.05
    GB, a 16 GB chip's whole memory (my chip runs, PR 39). The compiled
    form rounds differently in float32, so the families that were there
    keep the stock one."""

    def __init__(self, mean: float = 0.0, std: float = 1.0):
        self.mean, self.std = float(mean), float(std)

    def __call__(self, shape, dtype=None):
        from ..framework import core, random as fr
        dtype = core.convert_dtype(dtype) or core.get_default_dtype()
        return _normal_draw(fr.next_key(), tuple(shape), dtype, self.mean,
                            self.std)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal_draw(key, shape, dtype, mean, std):
    return mean + std * jax.random.normal(key, shape, dtype)


def linear(d_in, d_out, std, dtype, initializer=nn.initializer.Normal):
    attr = nn.ParamAttr(initializer=initializer(0.0, std))
    layer = nn.Linear(d_in, d_out, weight_attr=attr, bias_attr=False)
    created_in(layer.weight, dtype)
    return layer


def pre_norm(norm, x, eps):
    with jax.named_scope("norm"):
        # the repo's Pallas kernel: f32 inside, x's dtype out
        return fused_rms_norm(x, norm.weight._data, eps)


class SwiGLU(nn.Layer):
    """``(silu((a W1) g) * (a W3)) W2 d``, the product in float32;
    ``g`` / ``d`` are the gate and down multipliers of a model that
    scales its activations (both 1 elsewhere: nothing is multiplied)."""

    def __init__(self, hidden: int, width: int, std: float, dtype=None,
                 gate_multiplier: float = 1.0, down_multiplier: float = 1.0):
        super().__init__()
        self.gate_multiplier = float(gate_multiplier)
        self.down_multiplier = float(down_multiplier)
        self.w1 = linear(hidden, width, std, dtype)
        self.w3 = linear(hidden, width, std, dtype)
        self.w2 = linear(width, hidden, std, dtype)

    def run(self, a):
        gate = mm(a, self.w1).astype(jnp.float32)
        if self.gate_multiplier != 1.0:
            gate = gate * self.gate_multiplier
        up = jax.nn.silu(gate)
        out = mm((up * mm(a, self.w3).astype(jnp.float32))
                 .astype(a.dtype), self.w2)
        return out if self.down_multiplier == 1.0 \
            else out * jnp.asarray(self.down_multiplier, out.dtype)


class GroupedQueryAttention(nn.Layer):
    """``num_heads`` query heads over ``num_kv_heads`` key/value heads of
    ``head_dim``; q and k normed per head (``qk_norm``; a model without
    the norm has no such parameters) and rotated (rotate-half, base
    ``theta``; ``rotary=False``: a model whose positions come from
    elsewhere rotates nothing); ``key_scale`` multiplies the keys as
    projected."""

    def __init__(self, hidden: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, eps: float, theta: float, std: float,
                 dtype=None, qk_norm: bool = True, key_scale: float = 1.0,
                 rotary: bool = True):
        super().__init__()
        self.head_dim, self.eps, self.theta = head_dim, eps, float(theta)
        self.qk_norm, self.key_scale = bool(qk_norm), float(key_scale)
        self.rotary = bool(rotary)
        self.q_proj = linear(hidden, num_heads * head_dim, std, dtype)
        self.k_proj = linear(hidden, num_kv_heads * head_dim, std, dtype)
        self.v_proj = linear(hidden, num_kv_heads * head_dim, std, dtype)
        self.out_proj = linear(num_heads * head_dim, hidden, std, dtype)
        if self.qk_norm:
            self.q_norm = nn.RMSNorm(head_dim, epsilon=eps)
            self.k_norm = nn.RMSNorm(head_dim, epsilon=eps)

    def qkv(self, u, positions):
        """u ``[B, S, H]``, positions int ``[B, S]`` -> q ``[B, S, nh,
        hd]``, k, v ``[B, S, nkv, hd]``; q and k normed and rotated
        (where the model norms, where it rotates)."""
        B, S, _ = u.shape
        hd = self.head_dim
        q = mm(u, self.q_proj).reshape(B, S, -1, hd)
        k = mm(u, self.k_proj).reshape(B, S, -1, hd)
        if self.key_scale != 1.0:
            k = k * jnp.asarray(self.key_scale, k.dtype)
        v = mm(u, self.v_proj).reshape(B, S, -1, hd)
        if self.qk_norm:
            q = rms_head(q, self.q_norm.weight._data, self.eps)
            k = rms_head(k, self.k_norm.weight._data, self.eps)
        if not self.rotary:
            return q, k, v
        cos, sin = rope_tables(positions.reshape(-1), hd, self.theta)
        return fused_rope(q, cos, sin), fused_rope(k, cos, sin), v

    def full(self, u, causal_block: int = 1):
        """Causal attention over a whole sequence -> (Op, k, v); with
        ``causal_block`` B > 1 a position sees its whole block of B."""
        from ..kernels.attention import scaled_dot_product_attention
        B, S, _ = u.shape
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        q, k, v = self.qkv(u, pos)
        g = q.shape[2] // k.shape[2]
        a = scaled_dot_product_attention(
            Tensor(q), Tensor(jnp.repeat(k, g, axis=2)),
            Tensor(jnp.repeat(v, g, axis=2)), is_causal=True,
            causal_block=causal_block)._data
        return self.project(a.reshape(B, S, -1)), k, v

    def project(self, a):
        """The heads' outputs ``[..., nh * hd]`` through ``W_o``."""
        with jax.named_scope("out"):
            return mm(a.astype(self.out_proj.weight._data.dtype),
                      self.out_proj)


def rotate_half_rope_mxu(x, cos, sin):
    """:func:`rotate_half_rope` with ``rotate_half(x)`` taken as the
    product ``x P``, ``P = [[0, I], [-I, 0]]``: exact (every output is
    plus or minus one input), the same float32 arithmetic after it. For
    an ``x`` whose lanes are the minor axis of a large activation: there
    the compiler turns the two half slices and their concat into padded
    float32 arrays of their own (a quarter of a 128-lane tile each),
    while this contraction over the lanes reads ``x`` where it lies."""
    half = x.shape[-1] // 2
    eye, zero = np.eye(half), np.zeros((half, half))
    turn = jnp.asarray(np.block([[zero, eye], [-eye, zero]]), x.dtype)
    turned = jnp.dot(x, turn, precision=_mxu_precision(x),
                     preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + turned * sin).astype(x.dtype)


class Relu2MLP(nn.Layer):
    """``relu(a W_up)^2 W_down``: two matrices, no gate; the square in
    float32."""

    def __init__(self, hidden: int, width: int, std: float, dtype=None):
        super().__init__()
        self.up_proj = linear(hidden, width, std, dtype)
        self.down_proj = linear(width, hidden, std, dtype)

    def run(self, a):
        up = jax.nn.relu(mm(a, self.up_proj).astype(jnp.float32))
        return mm((up * up).astype(a.dtype), self.down_proj)


class Mamba2Mixer(nn.Layer):
    """The Mamba-2 state-space mixer::

        z | xBC | dt = split((u * in_multiplier) W_in * m)
        xBC' = silu(b + depthwise causal conv_{taps}(xBC))
        x [heads, d_head] | B [groups, d_state] | C [groups, d_state] = xBC'
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t
        y_t = H_t C_t + D x_t
        g = y * silu(z);  RMS over each group's d_inner / groups lanes,
        times the norm's weight
        out = g W_out                              d_inner = heads x d_head

    ``multipliers`` is a µP model's five factors on the lanes of z, x,
    B, C, dt (``m``) and ``in_multiplier`` its factor on ``u``; both act
    on activations at run time, and a model without them multiplies
    nothing. The recurrence is float32 (``kernels/ssd.py``): the chunked
    scan over a whole sequence (:meth:`full`), one step against kept
    state (:meth:`step`). Scopes (under the caller's ``ssm``):
    ``in_proj``, ``conv``, ``scan`` or ``step``, ``norm``, ``out``."""

    def __init__(self, hidden: int, heads: int, d_head: int, groups: int,
                 d_state: int, taps: int, chunk: int, eps: float, std: float,
                 dtype=None, in_multiplier: float = 1.0, multipliers=None):
        super().__init__()
        self.heads, self.d_head, self.groups = heads, d_head, groups
        self.d_state, self.taps, self.chunk, self.eps = (d_state, taps,
                                                         chunk, eps)
        d = self.d_inner = heads * d_head
        gn = groups * d_state
        self.conv_dim = d + 2 * gn
        self.in_multiplier = float(in_multiplier)
        self.in_proj = linear(hidden, d + self.conv_dim + heads, std, dtype)
        normal = nn.initializer.Normal(0.0, std)

        def small(shape, init=normal):
            return self.create_parameter(shape, dtype=dtype,
                                         default_initializer=init)

        self.conv_weight = small([taps, self.conv_dim])
        self.conv_bias = small([self.conv_dim])
        self.dt_bias = small([heads])
        self.A_log = small([heads])
        self.D = small([heads], nn.initializer.Constant(1.0))
        self.norm = nn.RMSNorm(d, epsilon=eps)
        self.out_proj = linear(d, hidden, std, dtype)
        # the µP vector: one multiplier a slice of the input projection
        self._mup = None
        if multipliers is not None:
            mz, mx, mb, mc, mdt = (float(v) for v in multipliers)
            self._mup = np.concatenate([
                np.full(d, mz), np.full(d, mx), np.full(gn, mb),
                np.full(gn, mc), np.full(heads, mdt)]).astype(np.float32)

    # -- the pieces both forms share ------------------------------------
    def project(self, u):
        """u ``[..., H]`` -> z ``[..., d_inner]``, xBC ``[..., conv_dim]``
        (before the convolution: what a decoder keeps the last ``taps -
        1`` positions of), dt ``[..., heads]`` as projected."""
        with jax.named_scope("in_proj"):
            if self.in_multiplier != 1.0:
                u = u * jnp.asarray(self.in_multiplier, u.dtype)
            p = mm(u, self.in_proj)
            if self._mup is not None:
                p = p * jnp.asarray(self._mup, u.dtype)
            d = self.d_inner
            return (p[..., :d], p[..., d:d + self.conv_dim],
                    p[..., d + self.conv_dim:])

    def split_heads(self, xbc):
        """The convolved ``[..., conv_dim]`` -> x ``[..., heads,
        d_head]``, B, C ``[..., groups, d_state]``."""
        d, gn = self.d_inner, self.groups * self.d_state
        lead = xbc.shape[:-1]
        return (xbc[..., :d].reshape(lead + (self.heads, self.d_head)),
                xbc[..., d:d + gn].reshape(lead + (self.groups,
                                                   self.d_state)),
                xbc[..., d + gn:].reshape(lead + (self.groups,
                                                  self.d_state)))

    def step_size(self, dt):
        """``softplus(dt + dt_bias)`` in float32 (no clamp: the
        published ``time_step_limit`` is (0, inf))."""
        return jax.nn.softplus(dt.astype(jnp.float32)
                               + self.dt_bias._data.astype(jnp.float32))

    def decay_rate(self):
        return -jnp.exp(self.A_log._data.astype(jnp.float32))

    def gate_and_project(self, y, z):
        """``(RMS_grouped(y * silu(z)) * weight) W_out``; y float32
        ``[..., heads, d_head]``, z ``[..., d_inner]``."""
        with jax.named_scope("norm"):
            g = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
            grouped = g.reshape(g.shape[:-1] + (self.groups, -1))
            grouped = grouped * jax.lax.rsqrt(
                jnp.mean(grouped * grouped, -1, keepdims=True) + self.eps)
            g = (grouped.reshape(g.shape)
                 * self.norm.weight._data.astype(jnp.float32)).astype(z.dtype)
        with jax.named_scope("out"):
            return mm(g, self.out_proj)

    # -- a whole sequence -----------------------------------------------
    def full(self, u, valid=None):
        """u ``[B, S, H]`` -> (out ``[B, S, H]``, xBC ``[B, S,
        conv_dim]`` before the convolution, the recurrent state ``[B,
        heads, d_head, d_state]`` float32 after the last VALID position:
        where ``valid [B, S]`` is false the step is 0 and the state
        stands still)."""
        from ..kernels.ssd import ssd_chunk_scan
        z, xbc, dt = self.project(u)
        with jax.named_scope("conv"):
            S = xbc.shape[1]
            padded = jnp.pad(xbc, ((0, 0), (self.taps - 1, 0), (0, 0)))
            w = self.conv_weight._data
            conv = self.conv_bias._data + sum(
                w[j] * padded[:, j:j + S] for j in range(self.taps))
            x, Bm, Cm = self.split_heads(jax.nn.silu(conv))
        with jax.named_scope("scan"):
            dt = self.step_size(dt)
            if valid is not None:
                dt = jnp.where(valid[..., None], dt, 0.0)
            A, D = self.decay_rate(), self.D._data
            y, H = jax.vmap(lambda *a: ssd_chunk_scan(
                a[0], a[1], A, a[2], a[3], D, self.chunk))(x, dt, Bm, Cm)
        return self.gate_and_project(y, z), xbc, H

    # -- one token --------------------------------------------------------
    def step(self, u, conv_state, recur):
        """u ``[B, H]``, conv_state ``[B, taps - 1, conv_dim]`` (the
        last xBC's, oldest first) -> (out ``[B, H]``, the state shifted
        by this xBC). ``recur(x, B, C, dt, A, D) -> y`` steps the
        recurrent state wherever it is kept (a dense array, a slot of
        the serving pool) and returns y ``[B, heads, d_head]`` f32."""
        z, xbc, dt = self.project(u)
        with jax.named_scope("conv"):
            window = jnp.concatenate(
                [conv_state.astype(xbc.dtype), xbc[:, None]], axis=1)
            conv = self.conv_bias._data + jnp.sum(
                window * self.conv_weight._data[None], axis=1)
            x, Bm, Cm = self.split_heads(jax.nn.silu(conv))
        with jax.named_scope("step"):
            y = recur(x, Bm, Cm, self.step_size(dt), self.decay_rate(),
                      self.D._data)
        return self.gate_and_project(y, z), window[:, 1:]
