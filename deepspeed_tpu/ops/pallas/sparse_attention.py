"""Block-sparse attention (Pallas) with DeepSpeed sparsity configs.

Reference parity: ``deepspeed/ops/sparse_attention/`` — the Triton
``matmul``/``softmax`` block-sparse kernels plus the ``SparsityConfig``
family (sparsity_config.py): Dense, Fixed, BigBird, BSLongformer.  The
reference builds a per-head block layout ``[H, NB, NB]`` (1 = block
computed) and runs sddmm → block softmax → dsd.

TPU translation: one Pallas kernel per (head, q-block) doing an
online-softmax sweep over k-blocks (flash style), with the layout row for
that q-block streamed in and applied as a block mask.  Blocks are
TPU-tile sized (128) so every matmul lands on the MXU.  Off-TPU the
kernel runs in interpreter mode; ``impl='xla'`` gives a pure-jnp
reference used by the parity tests.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...utils.platform import pallas_interpret


# --------------------------------------------------------------- layouts
@dataclasses.dataclass
class SparsityConfig:
    """Base layout builder (reference sparse_attention/sparsity_config.py)."""

    num_heads: int = 1
    block: int = 128  # TPU tile; reference default is 16 (GPU)

    def make_layout(self, seq_len: int) -> np.ndarray:
        raise NotImplementedError

    def _nb(self, seq_len: int) -> int:
        if seq_len % self.block:
            raise ValueError(f"seq_len {seq_len} not divisible by block "
                             f"{self.block}")
        return seq_len // self.block


@dataclasses.dataclass
class DenseSparsityConfig(SparsityConfig):
    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self._nb(seq_len)
        return np.ones((self.num_heads, nb, nb), bool)


@dataclasses.dataclass
class FixedSparsityConfig(SparsityConfig):
    """Local band + periodic global columns (reference
    FixedSparsityConfig: num_local_blocks band, num_global_blocks stride)."""

    num_local_blocks: int = 4
    num_global_blocks: int = 1

    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self._nb(seq_len)
        lay = np.zeros((self.num_heads, nb, nb), bool)
        for qi in range(nb):
            lo = (qi // self.num_local_blocks) * self.num_local_blocks
            lay[:, qi, lo:min(lo + self.num_local_blocks, nb)] = True
            # last num_global_blocks of each previous local window attend
            # globally (every row sees them)
            for w in range(0, qi + 1, self.num_local_blocks):
                g0 = max(w + self.num_local_blocks - self.num_global_blocks, 0)
                lay[:, qi, g0:min(w + self.num_local_blocks, nb)] = True
        return lay


@dataclasses.dataclass
class BSLongformerSparsityConfig(SparsityConfig):
    """Sliding window + designated global blocks (reference
    BSLongformerSparsityConfig)."""

    num_sliding_window_blocks: int = 3
    global_block_indices: tuple = (0,)

    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self._nb(seq_len)
        lay = np.zeros((self.num_heads, nb, nb), bool)
        half = self.num_sliding_window_blocks // 2
        for qi in range(nb):
            lay[:, qi, max(0, qi - half):min(nb, qi + half + 1)] = True
        for g in self.global_block_indices:
            if g < nb:
                lay[:, :, g] = True  # everyone attends to global
                lay[:, g, :] = True  # global attends to everyone
        return lay


@dataclasses.dataclass
class BigBirdSparsityConfig(SparsityConfig):
    """Random + sliding window + global (reference BigBirdSparsityConfig).
    Random blocks are sampled per head with a fixed seed (layouts must agree
    across data-parallel workers)."""

    num_random_blocks: int = 1
    num_sliding_window_blocks: int = 3
    num_global_blocks: int = 1
    seed: int = 0

    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self._nb(seq_len)
        lay = np.zeros((self.num_heads, nb, nb), bool)
        half = self.num_sliding_window_blocks // 2
        rng = np.random.RandomState(self.seed)
        for qi in range(nb):
            lay[:, qi, max(0, qi - half):min(nb, qi + half + 1)] = True
        g = min(self.num_global_blocks, nb)
        lay[:, :, :g] = True
        lay[:, :g, :] = True
        for h in range(self.num_heads):
            for qi in range(nb):
                for r in rng.choice(nb, size=min(self.num_random_blocks, nb),
                                    replace=False):
                    lay[h, qi, r] = True
        return lay


# --------------------------------------------------------------- kernels
def _sparse_attn_kernel(layout_ref, q_ref, k_ref, v_ref, o_ref, *,
                        sm_scale: float, causal: bool, block: int):
    # program: one (batch*head, q-block); refs carry a leading singleton from
    # the (1, ...) block specs: q [1, bq, d], k/v [1, S, d], layout [1, 1, NB]
    qi = pl.program_id(2)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [bq, d]
    S, D = k_ref.shape[1], k_ref.shape[2]
    nb = S // block

    m = jnp.full((block, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((block, 1), jnp.float32)
    acc = jnp.zeros((block, D), jnp.float32)

    def compute_block(kj, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kj * block, block), :]
        v_blk = v_ref[0, pl.ds(kj * block, block), :]
        s = q @ k_blk.astype(jnp.float32).T  # [bq, bk]
        if causal:
            qpos = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kj * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> nan
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(s), s - safe_m, -jnp.inf))
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + p @ v_blk.astype(jnp.float32)
        return m_new, l, acc

    def body(kj, carry):
        # the sparsity payoff: off-layout blocks skip the matmuls entirely
        # (lax.cond executes one branch at runtime)
        on = layout_ref[0, 0, kj] > 0
        return jax.lax.cond(on, lambda c: compute_block(kj, c),
                            lambda c: c, carry)

    # causal: k-blocks past the diagonal contribute nothing — don't visit
    upper = jnp.minimum(nb, qi + 1) if causal else nb
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     config: SparsityConfig, causal: bool = True,
                     impl: str = "pallas") -> jnp.ndarray:
    """q/k/v: [B, S, H, D] -> [B, S, H, D], block-sparse per ``config``.

    ``impl='xla'`` runs the jnp reference (dense compute, block mask) —
    the numeric oracle for the Pallas kernel.
    """
    B, S, H, D = q.shape
    layout = jnp.asarray(config.make_layout(S), jnp.int32)  # [H, NB, NB]
    if layout.shape[0] not in (1, H):
        raise ValueError(f"layout heads {layout.shape[0]} != {H}")
    if layout.shape[0] == 1:
        layout = jnp.broadcast_to(layout, (H, *layout.shape[1:]))
    sm_scale = 1.0 / math.sqrt(D)

    if impl == "xla":
        mask = jnp.kron(layout, jnp.ones((config.block, config.block),
                                         jnp.int32))  # [H, S, S]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
        big_neg = jnp.asarray(-jnp.inf, jnp.float32)
        s = jnp.where(mask[None] > 0, s, big_neg)
        if causal:
            cm = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(cm[None, None], s, big_neg)
        # rows with no visible keys: output 0
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)

    block = config.block
    nb = S // block
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    lay_bh = jnp.broadcast_to(layout[None], (B, H, nb, nb)).reshape(B * H, nb, nb)

    out = pl.pallas_call(
        functools.partial(_sparse_attn_kernel, sm_scale=sm_scale,
                          causal=causal, block=block),
        grid=(B * H, 1, nb),
        in_specs=[
            pl.BlockSpec((1, 1, nb), lambda bh, _, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block, D), lambda bh, _, qi: (bh, qi, 0)),
            pl.BlockSpec((1, S, D), lambda bh, _, qi: (bh, 0, 0)),
            pl.BlockSpec((1, S, D), lambda bh, _, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, D), lambda bh, _, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=pallas_interpret(),
    )(lay_bh, qt, kt, vt)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
