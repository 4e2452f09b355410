"""Block diffusion's noise process: what turns a batch of token sequences
into the batch `Transformer.loss` trains a block-diffusion model on
(`TransformerConfig.block_length` > 0; BD3-LMs, arXiv 2503.09573; SDAR,
arXiv 2510.06303).

A sequence of L tokens is L / block_length blocks. Each block b draws its
own time `t_b ~ U(diffusion_t_min, 1)`, and each of its tokens is replaced
by the mask id with probability `t_b`, independently (the linear schedule:
a token survives with `1 - t`). The loss of the sequence is the
masked-diffusion bound block by block,

    (1 / L) * sum_b (1 / t_b) * sum_{i in b, masked} -log p(x_i | noised b, clean blocks before b)

so a masked position weighs `1 / t_b` and every other position 0.

`noised` is part of the program, not of whoever feeds it: it runs inside
the jitted step (`loss_fn = lambda p, b: Transformer.loss(p,
diffusion.noised(b, cfg), cfg, ...)`), so the host draws tokens and hands
over a key a sequence, nothing else. Scope `diffusion/noise`
(models/transformer.py's vocabulary).
"""

from __future__ import annotations

from typing import Any, Dict

from ray_tpu.models.configs import TransformerConfig


def block_times(key, length: int, cfg: TransformerConfig):
    """One sequence's draws from its key: (t `[length / block_length]`
    f32, each block's time in `[diffusion_t_min, 1)`; u `[length]` f32,
    each token's own uniform draw), from the two halves of `key`. A token
    is masked where `u < t` of its block."""
    import jax
    import jax.numpy as jnp

    if not cfg.block_length or length % cfg.block_length:
        raise ValueError(
            f"block diffusion noises whole blocks: block_length "
            f"{cfg.block_length} does not divide sequences of {length} "
            f"tokens")
    k_t, k_u = jax.random.split(key)
    t = cfg.diffusion_t_min + (1.0 - cfg.diffusion_t_min) \
        * jax.random.uniform(k_t, (length // cfg.block_length,), jnp.float32)
    return t, jax.random.uniform(k_u, (length,), jnp.float32)


def noised(batch: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, Any]:
    """batch = {"tokens": [B, L] int32, "noise_key": a PRNG key a sequence
    ([B] typed keys, or threefry's two uint32 words each, [B, 2])} -> the
    batch of the block-diffusion loss:

        tokens   [B, L] int32  the noised copy: the mask id where masked
        targets  [B, L] int32  the clean tokens
        mask     [B, L] f32    1 / t of the position's block where it is
                               masked, 0 where it is not

    A sequence's noise is a function of its own key alone, so a batch
    sharded over its sequences draws shard by shard. Any other entry of
    the batch is kept."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("diffusion/noise"):
        clean, keys = batch["tokens"], batch["noise_key"]
        if not jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
            keys = jax.random.wrap_key_data(keys, impl="threefry2x32")
        t, u = jax.vmap(lambda key: block_times(key, clean.shape[1], cfg))(
            keys)
        t = jnp.repeat(t, cfg.block_length, axis=1)
        masked = u < t
        out = {name: leaf for name, leaf in batch.items()
               if name != "noise_key"}
        out.update(
            tokens=jnp.where(masked, jnp.asarray(cfg.mask_token,
                                                 clean.dtype), clean),
            targets=clean, mask=jnp.where(masked, 1.0 / t, 0.0))
        return out
