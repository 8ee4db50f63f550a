"""On-device augmentation: ship uint8, crop/mirror/mean-subtract in XLA.

TPU-first redesign of the host ``DataTransformer`` (ref:
caffe/src/caffe/util/data_transformer.cpp:19-119 — the reference's
augment runs per-sample on the host CPU and the GPU receives f32 crops).
Device-side, the host→HBM link carries full-size **uint8** instead of
cropped **f32** — 3.2× fewer bytes for the ImageNet recipe (256²×3 u8 =
196 KB/img vs 227²×3 f32 = 618 KB/img) — and the augment itself fuses
into the step's XLA program where it is bandwidth-trivial.  Matters most
when the feed link is the scarce resource (DCN-fed pods).

Semantics match ``DataTransformer`` exactly in TEST mode (deterministic
center crop: bit-identical outputs) and distributionally in TRAIN mode
(same mean→crop→mirror→scale order, per-sample uniform offsets and
mirror coin; the RNG is a JAX key rather than numpy, so draws differ).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sparknet_tpu.data.transform import TransformConfig


# device scope of the augment's ops; not ``L.``: a trace reader books
# ``L.<name>`` as a net layer
AUGMENT_SCOPE = "S.augment"


class DeviceAugment:
    """jit-compatible batch transform: uint8/float device array + PRNG
    key → float32 crops, in the INTERNAL layout (``Config.layout``,
    ``ops/layout.py``): (N, C, H, W) → (N, C, crop, crop) under nchw,
    (N, H, W, C) → (N, crop, crop, C) under nhwc.

    The nhwc path is where the data-formatting story closes end to end:
    image bytes arrive HWC off the wire (JPEG decoders, the record DB,
    ``data/minibatch.py``'s packers all see HWC first), so shipping
    (N, H, W, C) uint8 is the feed link's NATURAL orientation — zero
    entry transpose on either side of the link, and the augment fuses
    into a step whose convs already run channels-last.

    Use inside a jitted step, or as the ``device_fn`` of a
    :class:`~sparknet_tpu.data.prefetch.DevicePrefetcher` (the worker
    thread dispatches it asynchronously; the augment overlaps the
    previous step like the host transform did, minus the host work and
    the fat transfer).
    """

    def __init__(self, config: TransformConfig, layout: str | None = None):
        from sparknet_tpu.ops.layout import active_layout, normalize

        if config.mean_image is not None and config.mean_value:
            raise ValueError("specify mean_image or mean_value, not both")
        if config.backend != "numpy":
            raise ValueError(
                "DeviceAugment is its own backend; build the config with "
                "backend='numpy' (the default) and wrap it here"
            )
        self.config = config
        self.layout = normalize(layout) if layout else active_layout()
        mean = config.mean_image
        if mean is not None:
            mean = jnp.asarray(mean, jnp.float32)  # canonical (C, H, W)
            if self.layout == "nhwc":
                mean = mean.transpose(1, 2, 0)  # once, at construction
        self._mean = mean

    def __call__(self, images, key, train: bool = True):
        # the scope lands in the HLO metadata of a JITTED caller (the
        # trainer's aug4/aug5), so a trace names the augment's device
        # time.  Eager callers (device_fn below) dispatch op by op and
        # carry none: jax resets the name stack for an eager primitive
        with jax.named_scope(AUGMENT_SCOPE):
            return self._augment(images, key, train)

    def _augment(self, images, key, train: bool):
        cfg = self.config
        nhwc = self.layout == "nhwc"
        x = jnp.asarray(images).astype(jnp.float32)
        if nhwc:
            n, h, w, ch = x.shape
        else:
            n, ch, h, w = x.shape
        if self._mean is not None:
            x = x - self._mean[None]
        elif cfg.mean_value:
            mv = jnp.asarray(cfg.mean_value, jnp.float32)
            x = x - mv.reshape((1, 1, 1, -1) if nhwc else (1, -1, 1, 1))
        k_h, k_w, k_flip = jax.random.split(key, 3)
        c = cfg.crop_size
        if c:
            if h < c or w < c:
                raise ValueError(f"crop {c} larger than image {h}x{w}")
            if train:
                hos = jax.random.randint(k_h, (n,), 0, h - c + 1)
                wos = jax.random.randint(k_w, (n,), 0, w - c + 1)
            else:
                hos = jnp.full((n,), (h - c) // 2)
                wos = jnp.full((n,), (w - c) // 2)

            if nhwc:
                def one(img, ho, wo):
                    return jax.lax.dynamic_slice(img, (ho, wo, 0), (c, c, ch))
            else:
                def one(img, ho, wo):
                    return jax.lax.dynamic_slice(img, (0, ho, wo), (ch, c, c))

            x = jax.vmap(one)(x, hos, wos)
        if train and cfg.mirror:
            flip = jax.random.bernoulli(k_flip, 0.5, (n,))
            mirrored = x[:, :, ::-1, :] if nhwc else x[:, :, :, ::-1]
            x = jnp.where(flip[:, None, None, None], mirrored, x)
        if cfg.scale != 1.0:
            x = x * cfg.scale
        return x

    def device_fn(self, pid: int = 0, seed: int | None = None,
                  key_name: str = "data"):
        """The async-feed adapter: a ``device_fn(feeds, it)`` for the
        threaded prefetcher (:class:`~sparknet_tpu.data.prefetch.
        DevicePrefetcher`) or the process pipeline's device stage
        (:func:`~sparknet_tpu.data.pipeline.device_feed`) — one key
        policy for every source and both feed architectures
        (deterministic per process like the host transformer's
        ``seed=1234 + pid``; hosts decorrelate by pid, ``seed`` offsets
        the whole family so reruns can decorrelate)."""
        import jax

        base_key = jax.random.key(1234 + pid + (seed or 0))

        def fn(feeds, it):
            return {**feeds,
                    key_name: self(feeds[key_name],
                                   jax.random.fold_in(base_key, it))}

        return fn

    def trainer_device_fn(self, pid: int = 0, seed: int | None = None,
                          key_name: str = "data"):
        """The distributed-feed adapter: a ``fn(feeds, it)`` applied by
        ``ParallelTrainer``/``ElasticTrainer`` AFTER their own feed
        placement (``_put_feeds``/``_place_feeds``) and BEFORE the
        jitted round program — the tau path's uint8-wire hook, kept
        OUTSIDE the round program so every banked graph/mem manifest
        stays byte-identical.

        Key policy is the :meth:`device_fn` family unchanged — base key
        ``1234 + pid + seed``, ``fold_in(base, it)`` per round — with
        one extra fold for the leading axis: rank-5 feeds
        ([tau, B, ...] tau rounds, or [n, B, ...] scanned rounds) vmap
        the rank-4 augment with per-slot keys
        ``fold_in(fold_in(base, it), t)``, so slot t of round ``it``
        draws independently of every other slot and of any rank-4 run.
        Both arities are jitted per shape (the augment compiles once per
        feed geometry, off the round program)."""
        import jax

        base_key = jax.random.key(1234 + pid + (seed or 0))

        @jax.jit
        def aug4(x, key):
            return self(x, key)

        @jax.jit
        def aug5(x, key):
            keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(
                jnp.arange(x.shape[0]))
            return jax.vmap(lambda xs, ks: self(xs, ks))(x, keys)

        def fn(feeds, it):
            x = feeds[key_name]
            k = jax.random.fold_in(base_key, it)
            out = aug5(x, k) if jnp.ndim(x) == 5 else aug4(x, k)
            return {**feeds, key_name: out}

        return fn
