"""DiT — Diffusion Transformer (SD3/DiT family).

Capability parity target: the diffusion-transformer configs the
reference trains (SD3/DiT: conv + attention); reference
framework pieces: conv/attention kernels + fused layers (SURVEY.md §2.1
fused kernels). Architecture per the public DiT recipe: patchify conv →
N transformer blocks with adaLN-Zero timestep/label conditioning →
linear unpatchify predicting noise (and optionally sigma).

TPU notes: patchify is a stride-p conv (MXU-tiled by XLA); adaLN
modulation is elementwise and fuses into the surrounding matmuls; all
attention rides the same flash path as the LLMs.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp

from ..framework.core import apply
from .. import nn
from ..nn import functional as F

__all__ = ["DiTConfig", "DiT", "dit_tiny", "dit_s_2", "dit_xl_2"]


@dataclass
class DiTConfig:
    input_size: int = 32           # latent spatial size
    patch_size: int = 2
    in_channels: int = 4
    hidden_size: int = 1152
    depth: int = 28
    num_heads: int = 16
    mlp_ratio: float = 4.0
    num_classes: int = 1000
    class_dropout_prob: float = 0.1
    learn_sigma: bool = True
    dtype: str = "float32"
    use_recompute: bool = False


class TimestepEmbedder(nn.Layer):
    """Sinusoidal frequencies → 2-layer MLP."""

    def __init__(self, hidden_size, freq_dim=256, dtype="float32"):
        super().__init__(dtype=dtype)
        self.freq_dim = freq_dim
        self.mlp = nn.Sequential(
            nn.Linear(freq_dim, hidden_size), nn.Silu(),
            nn.Linear(hidden_size, hidden_size))

    def forward(self, t):
        half = self.freq_dim // 2

        def embed(ta):
            freqs = jnp.exp(-math.log(10000.0)
                            * jnp.arange(half, dtype=jnp.float32) / half)
            args = ta.astype(jnp.float32)[:, None] * freqs[None, :]
            return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
        emb = apply("timestep_embed", embed, t)
        return self.mlp(emb)


class LabelEmbedder(nn.Layer):
    """Class-label embedding with CFG dropout (extra 'null' class)."""

    def __init__(self, num_classes, hidden_size, dropout_prob,
                 dtype="float32"):
        super().__init__(dtype=dtype)
        self.num_classes = num_classes
        self.dropout_prob = dropout_prob
        self.embedding_table = nn.Embedding(num_classes + 1, hidden_size)

    def forward(self, labels):
        if self.training and self.dropout_prob > 0:
            from ..framework.core import default_generator
            import jax

            def drop(la):
                key = default_generator.next_key()
                keep = jax.random.uniform(key, la.shape) >= \
                    self.dropout_prob
                return jnp.where(keep, la, self.num_classes)
            labels = apply("cfg_drop", drop, labels)
        return self.embedding_table(labels)


def _modulate(x, shift, scale):
    return x * (1 + scale.unsqueeze(1)) + shift.unsqueeze(1)


class DiTBlock(nn.Layer):
    """Transformer block with adaLN-Zero conditioning."""

    def __init__(self, hidden_size, num_heads, mlp_ratio=4.0,
                 dtype="float32"):
        super().__init__(dtype=dtype)
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.norm1 = nn.LayerNorm(hidden_size, epsilon=1e-6,
                                  weight_attr=False, bias_attr=False)
        self.qkv = nn.Linear(hidden_size, 3 * hidden_size)
        self.proj = nn.Linear(hidden_size, hidden_size)
        self.norm2 = nn.LayerNorm(hidden_size, epsilon=1e-6,
                                  weight_attr=False, bias_attr=False)
        mlp_hidden = int(hidden_size * mlp_ratio)
        self.mlp = nn.Sequential(
            nn.Linear(hidden_size, mlp_hidden), nn.GELU(approximate=True),
            nn.Linear(mlp_hidden, hidden_size))
        # adaLN-Zero: 6 modulation vectors; final proj initialized to 0 so
        # each block starts as identity
        self.adaLN_modulation = nn.Sequential(
            nn.Silu(), nn.Linear(hidden_size, 6 * hidden_size))
        last = self.adaLN_modulation[1]
        last.weight.set_value(jnp.zeros_like(last.weight._value))
        last.bias.set_value(jnp.zeros_like(last.bias._value))

    def forward(self, x, c):
        mod = self.adaLN_modulation(c)
        (shift_msa, scale_msa, gate_msa, shift_mlp, scale_mlp,
         gate_mlp) = mod.chunk(6, axis=-1)
        b, s = x.shape[0], x.shape[1]
        h = _modulate(self.norm1(x), shift_msa, scale_msa)
        qkv = self.qkv(h).reshape([b, s, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn = F.scaled_dot_product_attention(q, k, v)
        attn = self.proj(attn.reshape([b, s, -1]))
        x = x + gate_msa.unsqueeze(1) * attn
        h = _modulate(self.norm2(x), shift_mlp, scale_mlp)
        return x + gate_mlp.unsqueeze(1) * self.mlp(h)


class FinalLayer(nn.Layer):
    def __init__(self, hidden_size, patch_size, out_channels,
                 dtype="float32"):
        super().__init__(dtype=dtype)
        self.norm_final = nn.LayerNorm(hidden_size, epsilon=1e-6,
                                       weight_attr=False, bias_attr=False)
        self.linear = nn.Linear(hidden_size,
                                patch_size * patch_size * out_channels)
        self.linear.weight.set_value(
            jnp.zeros_like(self.linear.weight._value))
        self.linear.bias.set_value(jnp.zeros_like(self.linear.bias._value))
        self.adaLN_modulation = nn.Sequential(
            nn.Silu(), nn.Linear(hidden_size, 2 * hidden_size))

    def forward(self, x, c):
        shift, scale = self.adaLN_modulation(c).chunk(2, axis=-1)
        return self.linear(_modulate(self.norm_final(x), shift, scale))


class DiT(nn.Layer):
    def __init__(self, cfg: DiTConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.out_channels = cfg.in_channels * (2 if cfg.learn_sigma else 1)
        self.x_embedder = nn.Conv2D(cfg.in_channels, cfg.hidden_size,
                                    cfg.patch_size, stride=cfg.patch_size)
        self.t_embedder = TimestepEmbedder(cfg.hidden_size,
                                           dtype=cfg.dtype)
        self.y_embedder = LabelEmbedder(cfg.num_classes, cfg.hidden_size,
                                        cfg.class_dropout_prob, cfg.dtype)
        n_patches = (cfg.input_size // cfg.patch_size) ** 2
        import jax
        from ..framework.core import default_generator, Parameter
        self.pos_embed = Parameter(
            0.02 * jax.random.normal(default_generator.next_key(),
                                     (1, n_patches, cfg.hidden_size),
                                     jnp.float32))
        self.blocks = nn.LayerList([
            DiTBlock(cfg.hidden_size, cfg.num_heads, cfg.mlp_ratio,
                     cfg.dtype) for _ in range(cfg.depth)])
        self.final_layer = FinalLayer(cfg.hidden_size, cfg.patch_size,
                                      self.out_channels, cfg.dtype)

    def unpatchify(self, x):
        c, p = self.out_channels, self.cfg.patch_size
        hw = int(math.isqrt(x.shape[1]))

        def f(xa):
            b = xa.shape[0]
            xa = xa.reshape(b, hw, hw, p, p, c)
            xa = jnp.einsum("bhwpqc->bchpwq", xa)
            return xa.reshape(b, c, hw * p, hw * p)
        return apply("unpatchify", f, x)

    def forward(self, x, t, y):
        """x: [B, C, H, W] noisy latents; t: [B] timesteps; y: [B]
        labels. Returns predicted noise [B, out_C, H, W]."""
        h = self.x_embedder(x)  # [B, hidden, H/p, W/p]
        b = h.shape[0]
        h = h.flatten(2).transpose([0, 2, 1])  # [B, N, hidden]
        h = h + self.pos_embed
        c = self.t_embedder(t) + self.y_embedder(y)
        if self.cfg.dtype != "float32":
            # pos_embed/embedders are f32 masters; narrow activations so
            # the block stack actually runs at the configured precision
            h = h.astype(self.cfg.dtype)
            c = c.astype(self.cfg.dtype)
        for block in self.blocks:
            if self.cfg.use_recompute:
                from ..distributed.fleet import recompute
                h = recompute(_BlockFn(block), h, c)
            else:
                h = block(h, c)
        h = self.final_layer(h, c)
        return self.unpatchify(h)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


class _BlockFn:
    def __init__(self, block):
        self.block = block

    def parameters(self):
        return self.block.parameters()

    def __call__(self, x, c):
        return self.block(x, c)


def dit_tiny(**kw) -> DiTConfig:
    return DiTConfig(input_size=8, patch_size=2, in_channels=4,
                     hidden_size=64, depth=2, num_heads=4, num_classes=10,
                     **kw)


def dit_s_2(**kw) -> DiTConfig:
    return DiTConfig(patch_size=2, hidden_size=384, depth=12, num_heads=6,
                     **kw)


def dit_xl_2(**kw) -> DiTConfig:
    return DiTConfig(patch_size=2, hidden_size=1152, depth=28,
                     num_heads=16, **kw)
