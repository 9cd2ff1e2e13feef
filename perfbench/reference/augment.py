"""Plain float32 two-crop augmentation (the MoCo v2 / v3 recipes).

Written from the published recipes (torchvision RandomResizedCrop,
ColorJitter, RandomGrayscale, GaussianBlur, RandomSolarize, Normalize) in
straightforward `jax.numpy`, one image at a time under `vmap`. It imports
nothing of the program. What it shares with the program is the *stream of
random draws*: the step's key is `fold_in(data_key, step)`, split in two for
the views, folded by the row's index, and split per transform in the order
written below, so that both sides crop the same box, draw the same jitter
factors and blur with the same sigma. Every draw is float32 on both sides;
the pixels are float32 here and bfloat16 in the program.

`keep` is the type the picture is kept in between transforms: the identity for
the float32 reference; a rounding where the reference stands in for a lower
`compute_dtype` (the control), which in the program types the augmentation too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MEAN = np.array([0.485, 0.456, 0.406], np.float32)
STD = np.array([0.229, 0.224, 0.225], np.float32)
HI = jax.lax.Precision.HIGHEST

# one view's recipe: the keys a later configuration may set in its file
DEFAULT_VIEW = dict(
    out_size=224, min_scale=0.2, max_scale=1.0, brightness=0.4, contrast=0.4,
    saturation=0.4, hue=0.1, jitter_prob=0.8, grayscale_prob=0.2,
    blur_prob=0.5, blur_sigma=(0.1, 2.0), flip_prob=0.5, solarize_prob=0.0,
    rrc_trials=10,
)


def view(**changes) -> dict:
    return dict(DEFAULT_VIEW, **changes)


def _gray(x):
    return x[..., 0] * 0.299 + x[..., 1] * 0.587 + x[..., 2] * 0.114


def _rgb_to_hsv(rgb):
    r, g, b = rgb[..., 0], rgb[..., 1], rgb[..., 2]
    hi = jnp.max(rgb, axis=-1)
    lo = jnp.min(rgb, axis=-1)
    d = hi - lo
    sd = jnp.where(d == 0, 1.0, d)
    s = jnp.where(hi == 0, 0.0, d / jnp.where(hi == 0, 1.0, hi))
    rc, gc, bc = (hi - r) / sd, (hi - g) / sd, (hi - b) / sd
    h = jnp.where(hi == r, bc - gc,
                  jnp.where(hi == g, 2.0 + rc - bc, 4.0 + gc - rc))
    h = jnp.where(d == 0, 0.0, h / 6.0) % 1.0
    return h, s, hi


def _hsv_to_rgb(h, s, v):
    i = jnp.floor(h * 6.0)
    f = h * 6.0 - i
    p, q, t = v * (1 - s), v * (1 - s * f), v * (1 - s * (1 - f))
    i = i.astype(jnp.int32) % 6
    table = jnp.stack([
        jnp.stack([v, t, p], -1), jnp.stack([q, v, p], -1),
        jnp.stack([p, v, t], -1), jnp.stack([p, q, v], -1),
        jnp.stack([t, p, v], -1), jnp.stack([v, p, q], -1)])
    return jnp.take_along_axis(table, i[None, ..., None], axis=0)[0]


def _crop_box(key, ext_h, ext_w, v):
    """torchvision `RandomResizedCrop.get_params`: ten draws of (area,
    log-ratio), the first that fits wins, else the centred fallback."""
    ka, kr, ky, kx = jax.random.split(key, 4)
    n = v["rrc_trials"]
    area = ext_h * ext_w * jax.random.uniform(
        ka, (n,), minval=v["min_scale"], maxval=v["max_scale"])
    ratio = jnp.exp(jax.random.uniform(
        kr, (n,), minval=np.log(3 / 4), maxval=np.log(4 / 3)))
    ws, hs = jnp.sqrt(area * ratio), jnp.sqrt(area / ratio)
    fits = (ws <= ext_w) & (hs <= ext_h) & (ws >= 1.0) & (hs >= 1.0)
    first, found = jnp.argmax(fits), jnp.any(fits)
    aspect = ext_w / ext_h
    fb_w = jnp.where(aspect < 0.75, ext_w,
                     jnp.where(aspect > 4 / 3, ext_h * (4 / 3), ext_w))
    fb_h = jnp.where(aspect < 0.75, ext_w / 0.75, ext_h)
    cw, ch = jnp.where(found, ws[first], fb_w), jnp.where(found, hs[first], fb_h)
    y0 = jnp.where(found, jax.random.uniform(ky) * (ext_h - ch), (ext_h - ch) / 2)
    x0 = jnp.where(found, jax.random.uniform(kx) * (ext_w - cw), (ext_w - cw) / 2)
    return y0, x0, ch, cw


def _resample_rows(src, out, start, size, valid):
    """[out, src] weights of antialiased bilinear resampling (PIL's triangle
    filter, widened by the minification) of the window [start, start+size),
    over the `valid` leading rows of the source only."""
    scale = size / out
    centre = start + (jnp.arange(out, dtype=jnp.float32) + 0.5) * scale - 0.5
    idx = jnp.arange(src, dtype=jnp.float32)
    w = jnp.clip(1.0 - jnp.abs(centre[:, None] - idx[None]) / jnp.maximum(scale, 1.0), 0.0)
    w = w * (idx[None] < valid)
    return w / jnp.maximum(w.sum(1, keepdims=True), 1e-8)


def _blur(img, key, v):
    ks, kp = jax.random.split(key)
    lo, hi = v["blur_sigma"]
    sigma = jax.random.uniform(ks, (), minval=lo, maxval=hi)
    r = max(1, int(0.05 * v["out_size"]))
    taps = jnp.exp(-0.5 * (jnp.arange(-r, r + 1, dtype=jnp.float32) / sigma) ** 2)
    taps = taps / taps.sum()
    applied = jax.random.uniform(kp, ()) < v["blur_prob"]
    n = img.shape[0]
    pad = jnp.pad(img, ((r, r), (r, r), (0, 0)), mode="edge")
    rows = sum(taps[i] * pad[i:i + n] for i in range(2 * r + 1))
    out = sum(taps[i] * rows[:, i:i + n] for i in range(2 * r + 1))
    return jnp.where(applied, out, img)


def _jitter(img, key, v, keep):
    kb, kc, ks, kh, kp, kperm = jax.random.split(key, 6)

    def factor(k, amount):
        return jax.random.uniform(k, (), minval=max(0.0, 1 - amount), maxval=1 + amount)

    fb, fc, fs = (factor(kb, v["brightness"]), factor(kc, v["contrast"]),
                  factor(ks, v["saturation"]))
    shift = jax.random.uniform(kh, (), minval=-v["hue"], maxval=v["hue"])
    order = jax.random.permutation(kperm, 4)

    def hue(x):
        h, s, val = _rgb_to_hsv(x)
        return _hsv_to_rgb((h + shift) % 1.0, s, val)

    ops = [
        lambda x: jnp.clip(x * fb, 0, 1),
        lambda x: jnp.clip((x - jnp.mean(_gray(x))) * fc + jnp.mean(_gray(x)), 0, 1),
        lambda x: jnp.clip((x - _gray(x)[..., None]) * fs + _gray(x)[..., None], 0, 1),
        hue if v["hue"] > 0 else (lambda x: x),
    ]
    out = img
    for slot in range(4):
        out = keep(jax.lax.switch(order[slot], ops, out))
    return jnp.where(jax.random.uniform(kp, ()) < v["jitter_prob"], out, img)


def augment_one(img_u8, key, extent, v, keep=lambda x: x):
    """One view of one staged image: `extent` = (valid_h, valid_w, rot)."""
    kcrop, kjit, kgray, kblur, kflip, ksol = jax.random.split(key, 6)
    img = keep(img_u8.astype(jnp.float32) / 255.0)
    eh, ew = extent[0].astype(jnp.float32), extent[1].astype(jnp.float32)
    rot = extent[2] > 0
    y0, x0, ch, cw = _crop_box(kcrop, eh, ew, v)
    flip = jax.random.uniform(kflip, ()) < v["flip_prob"]
    size = v["out_size"]
    rv = _resample_rows(img.shape[0], size, y0, ch, eh)
    rh = _resample_rows(img.shape[1], size, x0, cw, ew)
    rv = jnp.where(flip & rot, rv[::-1], rv)
    rh = jnp.where(flip & ~rot, rh[::-1], rh)
    img = keep(jnp.einsum("oh,hwc,pw->opc", rv, img, rh, precision=HI))
    img = jnp.where(rot, jnp.swapaxes(img, 0, 1), img)
    if v["jitter_prob"] > 0:
        img = _jitter(img, kjit, v, keep)
    if v["grayscale_prob"] > 0:
        grey = jnp.broadcast_to(_gray(img)[..., None], img.shape)
        img = keep(jnp.where(jax.random.uniform(kgray, ()) < v["grayscale_prob"], grey, img))
    if v["blur_prob"] > 0:
        img = keep(_blur(img, kblur, v))
    if v["solarize_prob"] > 0:
        sol = jnp.where(img >= 0.5, 1.0 - img, img)
        img = jnp.where(jax.random.uniform(ksol, ()) < v["solarize_prob"], sol, img)
    return keep((img - MEAN) / STD)


def two_crops(imgs_u8, extents, data_key, step, views, keep=lambda x: x):
    """`[B,H,W,3]` uint8 -> two `[B,S,S,3]` float32 views; `views` is the pair
    of recipes (the same twice for v2)."""
    kq, kk = jax.random.split(jax.random.fold_in(data_key, step))
    rows = jnp.arange(imgs_u8.shape[0])

    def one_view(k, v):
        keys = jax.vmap(lambda i: jax.random.fold_in(k, i))(rows)
        return jax.vmap(lambda im, kk_, ex: augment_one(im, kk_, ex, v, keep))(
            imgs_u8, keys, extents)

    return one_view(kq, views[0]), one_view(kk, views[1])
