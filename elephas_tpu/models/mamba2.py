"""Mamba-2 (SSD) mixer: the state-space half of a hybrid block.

A block of a hybrid model (Falcon-H1) runs this mixer and grouped-query
attention side by side on the same normed input and adds their outputs
(:mod:`~elephas_tpu.models.transformer`). With ``u`` the normed input, the
sizes of :class:`Mamba2Mixer` and ``mup`` the config's five projection
multipliers spread over the segments of ``w_in``::

    p           = (u * ssm_in) @ w_in * mup          # [z | x | B | C | dt]
    z, xBC, dt  = split(p, [d_ssm, conv_dim, heads])
    xBC         = silu(causal_depthwise_conv1d(xBC; conv_w, conv_b))
    x, B, C     = split(xBC, [d_ssm, groups * d_state, groups * d_state])
    dt          = softplus(dt + dt_bias);  a_t = exp(dt_t * -exp(A_log))
    S_t         = a_t * S_{t-1} + dt_t * x_t (outer) B_t     # per head
    y_t         = S_t @ C_t + D * x_t
    m           = group_rmsnorm(y * silu(z); norm) @ w_out * ssm_out

What a row keeps between calls is per SLOT, not per position
(:func:`state_leaves`): ``conv``, the last ``d_conv - 1`` inputs of the
convolution in the compute dtype, and ``ssm``, the state ``S`` of every
head, ``(heads, head_dim, d_state)`` in ``state_dtype`` (float32).

The recurrence has the two forms a serving engine runs:

- :func:`ssd_chunk_scan` -- a chunk of a prompt, given the state before it
  and returning the state after it: within a block of ``chunk`` tokens the
  masked ``C B^T`` product, between blocks the carried state; matrix
  products, not a scan over the tokens;
- :func:`ssd_update` -- one token a row, the decode step: the state read
  and written once, in float32.

:func:`mixer_apply` picks between them by the number of tokens it is
given. The decays, ``softplus`` and the state are float32; the matrix
products take their operands in the compute dtype and accumulate in
float32. Device scopes: ``elephas.ssm.project``, ``.conv``, ``.scan``
(chunks), ``.update`` (the step), ``.norm``, ``.out``.
"""
import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["Mamba2Mixer", "init_mixer", "mixer_specs", "state_leaves",
           "zero_layer_state", "zero_state", "mixer_apply", "project", "causal_conv",
           "ssd_chunk_scan", "ssd_update", "gated_group_norm"]


@dataclasses.dataclass(frozen=True)
class Mamba2Mixer:
    """Sizes of the state-space mixer every layer carries beside its
    attention (``TransformerConfig.ssm``; None: no mixer)."""
    d_ssm: int
    heads: int
    head_dim: int
    groups: int
    d_state: int
    d_conv: int = 4
    #: tokens in one block of :func:`ssd_chunk_scan`
    chunk: int = 128
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.d_ssm != self.heads * self.head_dim:
            raise ValueError(f"d_ssm {self.d_ssm} != heads {self.heads} x "
                             f"head_dim {self.head_dim}")
        if self.groups < 1 or self.heads % self.groups \
                or self.d_ssm % self.groups:
            raise ValueError(f"groups ({self.groups}) must divide heads "
                             f"({self.heads})")
        if self.d_conv < 2 or self.chunk < 1 or self.d_state < 1:
            raise ValueError("d_conv >= 2, chunk >= 1 and d_state >= 1")

    @property
    def conv_dim(self) -> int:
        """Width the convolution runs over: ``[x | B | C]``."""
        return self.d_ssm + 2 * self.groups * self.d_state

    @property
    def in_dim(self) -> int:
        """Columns of ``w_in``: ``[z | x | B | C | dt]``."""
        return self.d_ssm + self.conv_dim + self.heads

    @property
    def segments(self) -> Tuple[int, ...]:
        """Widths of the five segments of ``w_in``'s columns."""
        gn = self.groups * self.d_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.heads)


def init_mixer(config, key, dense) -> Dict:
    """One layer's mixer parameters, Mamba-2's published initialisation:
    ``A_log = log(uniform(1, 16))``, ``dt_bias`` the inverse softplus of a
    log-uniform 0.001-0.1, ``D`` and the gated norm's weight ones, the
    convolution uniform in +-1/sqrt(d_conv)."""
    c, m = config, config.ssm
    k = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(k[3], (m.heads,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    bound = 1.0 / math.sqrt(m.d_conv)
    return {
        "w_in": dense(k[0], (c.d_model, m.in_dim), c.d_model),
        "conv_w": jax.random.uniform(k[1], (m.d_conv, m.conv_dim),
                                     jnp.float32, -bound, bound
                                     ).astype(c.param_dtype),
        "conv_b": jnp.zeros((m.conv_dim,), c.param_dtype),
        "A_log": jnp.log(jax.random.uniform(
            k[2], (m.heads,), jnp.float32, 1.0, 16.0)).astype(c.param_dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(c.param_dtype),
        "D": jnp.ones((m.heads,), c.param_dtype),
        "norm": jnp.ones((m.d_ssm,), c.param_dtype),
        "w_out": dense(k[4], (m.d_ssm, c.d_model), m.d_ssm),
    }


def mixer_specs(P) -> Dict:
    """PartitionSpecs of :func:`init_mixer`'s tree: replicated (the mixer
    has no tensor-parallel form yet)."""
    return {"w_in": P(None, None), "conv_w": P(None, None),
            "conv_b": P(None), "A_log": P(None), "dt_bias": P(None),
            "D": P(None), "norm": P(None), "w_out": P(None, None)}


def state_leaves(mixer: Mamba2Mixer, dtype) -> Dict[str, Tuple[Tuple, Any]]:
    """What one layer keeps per slot, ``{leaf: (shape, dtype)}``."""
    return {"conv": ((mixer.d_conv - 1, mixer.conv_dim), dtype),
            "ssm": ((mixer.heads, mixer.head_dim, mixer.d_state),
                    mixer.state_dtype)}


def zero_layer_state(config, rows: int) -> Dict:
    """One layer's state for ``rows`` rows that have seen no token."""
    return {name: jnp.zeros((rows, *shape), dtype) for name, (shape, dtype)
            in state_leaves(config.ssm, config.dtype).items()}


def zero_state(config, rows: int) -> Dict:
    """Every layer's :func:`zero_layer_state`: ``{"layer_i": {"conv",
    "ssm"}}`` with a leading ``rows`` axis."""
    return {f"layer_{i}": zero_layer_state(config, rows)
            for i in range(config.num_layers)}


# ------------------------------------------------------------------ pieces
def _mup_vector(config) -> jnp.ndarray:
    """The five projection multipliers spread over ``w_in``'s columns."""
    return jnp.concatenate([jnp.full((width,), scale, jnp.float32)
                            for width, scale in zip(config.ssm.segments,
                                                    config.multipliers.ssm)])


def project(p: Dict, u: jnp.ndarray, config):
    """``u`` ``(B, T, D)`` -> ``z`` ``(B, T, d_ssm)``, ``xBC`` ``(B, T,
    conv_dim)`` (the convolution's input) and the raw ``dt`` ``(B, T,
    heads)``."""
    c, m = config, config.ssm
    with jax.named_scope("elephas.ssm.project"):
        if c.multipliers is not None:
            u = u * c.multipliers.ssm_in
        proj = u.astype(c.dtype) @ p["w_in"].astype(c.dtype)
        if c.multipliers is not None:
            proj = proj * _mup_vector(c).astype(c.dtype)
        z, xbc, dt = jnp.split(proj, [m.d_ssm, m.d_ssm + m.conv_dim],
                               axis=-1)
    return z, xbc, dt


def causal_conv(p: Dict, xbc: jnp.ndarray, carried: jnp.ndarray):
    """Depthwise causal convolution of width ``d_conv`` over ``xbc``
    ``(B, T, conv_dim)`` with the ``d_conv - 1`` inputs that came before
    it in front (``carried`` ``(B, d_conv - 1, conv_dim)``; zeros at a
    sequence's start), then SiLU. One token (``T`` = 1) is the same sum
    over a window of ``d_conv``. Returns the output ``(B, T, conv_dim)``
    and the inputs to carry on: the window's last ``d_conv - 1``."""
    with jax.named_scope("elephas.ssm.conv"):
        t = xbc.shape[1]
        window = jnp.concatenate([carried.astype(xbc.dtype), xbc], axis=1)
        w = p["conv_w"].astype(jnp.float32)
        taps = w.shape[0]
        out = p["conv_b"].astype(jnp.float32) + sum(
            window[:, k:k + t].astype(jnp.float32) * w[k]
            for k in range(taps))
        return (jax.nn.silu(out).astype(xbc.dtype),
                window[:, t:].astype(carried.dtype))


def ssd_chunk_scan(x, dt, A, B, C, state0, chunk: int):
    """The selective scan over ``T`` tokens in blocks of ``chunk``.

    ``x`` ``(Bt, T, H, P)``, ``dt`` ``(Bt, T, H)`` float32 (after
    softplus), ``A`` ``(H,)`` float32 (negative), ``B`` and ``C`` ``(Bt,
    T, G, N)`` (head ``i`` uses group ``i // (H / G)``), ``state0``
    ``(Bt, H, P, N)``. Returns ``y`` ``(Bt, T, H, P)`` float32 (without
    the ``D`` skip) and the state after the last token, float32.

    Within a block, ``y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s)
    dt_s x_s`` is one masked ``(L, L)`` product a head; a block hands the
    next its state, decayed by the block's total; what earlier blocks
    left reaches ``y_t`` as ``exp(cum_t) S_prev @ C_t``. ``T`` is padded
    to whole blocks with ``dt = 0``, which neither decays nor feeds the
    state."""
    bt, t, h, p = x.shape
    g, n = B.shape[2:]
    hg = h // g
    size = min(int(chunk), t)
    nc = -(-t // size)
    pad = nc * size - t
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    dtype = x.dtype
    x = x.reshape(bt, nc, size, g, hg, p)
    B = B.reshape(bt, nc, size, g, n)
    C = C.reshape(bt, nc, size, g, n)
    dt = dt.reshape(bt, nc, size, g, hg)
    cum = jnp.cumsum(dt * A.reshape(g, hg), axis=2)       # (Bt, nc, L, G, hg)
    # within a block
    seg = cum[:, :, :, None] - cum[:, :, None, :]         # (.., Lt, Ls, G, hg)
    tril = jnp.tril(jnp.ones((size, size), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(tril, seg, -jnp.inf))
    cb = jnp.einsum("bclgn,bcsgn->bclsg", C, B,
                    preferred_element_type=jnp.float32)
    xdt = (x * dt[..., None]).astype(dtype)
    y = jnp.einsum("bclsgh,bcsghp->bclghp",
                   (cb[..., None] * decay).astype(dtype), xdt,
                   preferred_element_type=jnp.float32)
    # what each block leaves behind, and the state before each block
    to_end = jnp.exp(cum[:, :, -1:] - cum)                # (Bt, nc, L, G, hg)
    left = jnp.einsum("bcsghp,bcsgn->bcghpn",
                      (x * (dt * to_end)[..., None]).astype(dtype), B,
                      preferred_element_type=jnp.float32)
    total = jnp.exp(cum[:, :, -1])                        # (Bt, nc, G, hg)

    def carry_on(state, block):
        added, kept = block
        return state * kept[..., None, None] + added, state

    last, before = jax.lax.scan(
        carry_on, state0.astype(jnp.float32).reshape(bt, g, hg, p, n),
        (jnp.moveaxis(left, 1, 0), jnp.moveaxis(total, 1, 0)))
    y = y + jnp.einsum("bclgn,cbghpn->bclghp", C, before.astype(dtype),
                       preferred_element_type=jnp.float32
                       ) * jnp.exp(cum)[..., None]
    return (y.reshape(bt, nc * size, h, p)[:, :t],
            last.reshape(bt, h, p, n))


def ssd_update(state, x, dt, A, B, C):
    """One token a row: ``state`` ``(Bt, H, P, N)``, ``x`` ``(Bt, H, P)``,
    ``dt`` ``(Bt, H)`` float32, ``A`` ``(H,)``, ``B`` and ``C`` ``(Bt, G,
    N)``. Returns ``y`` ``(Bt, H, P)`` float32 (without the ``D`` skip)
    and the new state, float32: the state is read once and written once,
    everything else is a few vectors a row."""
    bt, h, p = x.shape
    g, n = B.shape[1:]
    hg = h // g
    s = state.astype(jnp.float32).reshape(bt, g, hg, p, n)
    dt = dt.reshape(bt, g, hg)
    kept = jnp.exp(dt * A.reshape(g, hg))
    fed = (x.astype(jnp.float32).reshape(bt, g, hg, p) * dt[..., None])
    s = (s * kept[..., None, None]
         + fed[..., None] * B.astype(jnp.float32)[:, :, None, None, :])
    # a product and a sum, not a matmul: the state stays float32 on the
    # TPU, where a default-precision dot would round it to bfloat16
    y = jnp.sum(s * C.astype(jnp.float32)[:, :, None, None, :], axis=-1)
    return y.reshape(bt, h, p), s.reshape(bt, h, p, n)


def gated_group_norm(p: Dict, y, z, config):
    """``group_rmsnorm(y * silu(z))``: the gate first
    (``mamba_norm_before_gate`` false), then RMSNorm over each of the
    ``groups`` slices of ``d_ssm``, times the weight."""
    c, m = config, config.ssm
    with jax.named_scope("elephas.ssm.norm"):
        y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        shape = y.shape
        y = y.reshape(*shape[:-1], m.groups, m.d_ssm // m.groups)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1,
                                       keepdims=True) + c.rms_norm_eps)
        return (y.reshape(shape) * p["norm"].astype(jnp.float32)
                ).astype(c.dtype)


# ----------------------------------------------------------------- mixer
def mixer_apply(p: Dict, u: jnp.ndarray, state: Dict, config):
    """The mixer over ``u`` ``(B, T, D)`` (the block's normed input) from
    ``state`` ``{"conv", "ssm"}`` (leading axis ``B``). Returns the
    mixer's output ``(B, T, D)`` (``ssm_out`` applied) and the state
    after the last token. ``T`` = 1 is the decode step
    (:func:`ssd_update`); more tokens run :func:`ssd_chunk_scan`."""
    c, m = config, config.ssm
    b, t, _ = u.shape
    z, xbc, dt = project(p, u, c)
    xbc, conv = causal_conv(p, xbc, state["conv"])
    gn = m.groups * m.d_state
    x = xbc[..., :m.d_ssm].reshape(b, t, m.heads, m.head_dim)
    B = xbc[..., m.d_ssm:m.d_ssm + gn].reshape(b, t, m.groups, m.d_state)
    C = xbc[..., m.d_ssm + gn:].reshape(b, t, m.groups, m.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if t == 1:
        with jax.named_scope("elephas.ssm.update"):
            y, ssm = ssd_update(state["ssm"], x[:, 0], dt[:, 0], A,
                                B[:, 0], C[:, 0])
            y = y[:, None]
    else:
        with jax.named_scope("elephas.ssm.scan"):
            y, ssm = ssd_chunk_scan(x, dt, A, B, C, state["ssm"], m.chunk)
    y = y + x.astype(jnp.float32) * p["D"].astype(jnp.float32)[:, None]
    y = gated_group_norm(p, y.reshape(b, t, m.d_ssm), z, c)
    with jax.named_scope("elephas.ssm.out"):
        out = y @ p["w_out"].astype(c.dtype)
        if c.multipliers is not None:
            out = out * c.multipliers.ssm_out
    return out, {"conv": conv, "ssm": ssm.astype(m.state_dtype)}
