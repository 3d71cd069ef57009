"""A vision transformer classifier, ``program.family`` ``vit``.

The program's ``init_vit``, ``init_vit_states`` and ``vit_loss``, with
``program.n_classes`` classes. A row is an image: its patches (cutting
the image into them is the host's) and the class token, ``seq`` tokens in
all, the patches' values drawn N(0, 1), the label uniformly over the
classes.
"""
from __future__ import annotations

import flops


def shape(cfg: dict) -> tuple[int, int]:
    """(patches per image, values per patch)."""
    m = cfg["model"]
    side = m["image_size"] // m["patch_size"]
    return side * side, m["patch_size"] ** 2 * m["num_channels"]


def init_params(cfg: dict, mcfg, key, dtype):
    from repro.models.vit import init_vit

    n, p = shape(cfg)
    return init_vit(key, mcfg, cfg["program"]["n_classes"], patch_dim=p,
                    n_patches=n, dtype=dtype)


def init_states(cfg: dict, mcfg, key, batch: int, seq: int, dtype):
    from repro.models.vit import init_vit_states

    return init_vit_states(key, mcfg, batch, seq - 1, dtype)


def loss(cfg: dict):
    from repro.models.vit import vit_loss

    return vit_loss


def draw_batches(cfg: dict, mix: dict, n: int, key) -> dict:
    """``n`` batches stacked on a leading axis, each row different."""
    import jax
    import jax.numpy as jnp

    patches, width = shape(cfg)
    if mix["seq"] != patches + 1:
        raise ValueError(f"a row of {cfg['name']} is {patches + 1} tokens, "
                         f"the traffic says seq {mix['seq']}")
    b = mix["batch"]
    kp, kl = jax.random.split(key)
    return {"patches": jax.random.normal(kp, (n, b, patches, width),
                                         jnp.float32),
            "labels": jax.random.randint(kl, (n, b), 0,
                                         cfg["program"]["n_classes"],
                                         jnp.int32)}


def image_flops(cfg: dict) -> float:
    """Forward operations of one image: the patch embedding, every layer
    over all tokens (patches and the class token, each attending over all
    of them), and the class head on the class token alone."""
    patches, width = shape(cfg)
    d = cfg["model"]["hidden_size"]
    tokens = patches + 1
    return (2 * patches * width * d + tokens * flops.stack_flops(cfg, tokens)
            + 2 * d * cfg["program"]["n_classes"])


def step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward + backward operations of a step of ``batch`` images."""
    return 3 * batch * image_flops(cfg)
