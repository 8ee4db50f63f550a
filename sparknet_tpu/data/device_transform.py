"""On-device augmentation: ship uint8, crop/mirror/mean-subtract on the chip.

TPU-first redesign of the host ``DataTransformer`` (ref:
caffe/src/caffe/util/data_transformer.cpp:19-119 — the reference's
augment runs per-sample on the host CPU and the GPU receives f32 crops).
Device-side, the host→HBM link carries full-size **uint8** instead of
cropped **f32** — 3.2× fewer bytes for the ImageNet recipe (256²×3 u8 =
196 KB/img vs 227²×3 f32 = 618 KB/img).  Matters most when the feed link
is the scarce resource (DCN-fed pods).

What each adapter dispatches (neither is fused into the step's program:
both run before it, on the stream the step runs on):

- :meth:`DeviceAugment.device_fn`, the solo feed's (the prefetcher's
  worker, the process pipeline's device stage): ONE jitted program a
  batch under the ``S.augment`` scope.  Where :meth:`DeviceAugment.
  takes_fused` says so (a uint8 NCHW batch on a TPU, a crop, no per-pixel
  mean, shapes that tile) that program is :func:`crop_mirror`: it reads
  the uint8 batch once and writes the f32 crop once, crop and mirror act
  on the uint8 VALUES and the convert and the mean on the crop, so no
  array of the input's height × width exists in f32.  Elsewhere it
  dispatches ``_augment`` op by op, as it always did.
- :meth:`DeviceAugment.trainer_device_fn`, the τ path's: a jitted
  ``_augment`` (``aug4`` / ``aug5``), a program of its own whose gather
  reads a full-size f32 batch.  It moves over to the one pass by one
  call once the trainer cell's feed thread is no longer level with its
  device round (ROADMAP S7 d / S11).

``_augment`` is also the oracle: the one pass draws the offsets and
flips ``_augment`` draws for a key and equals it bit for bit.

Semantics match ``DataTransformer`` exactly in TEST mode (deterministic
center crop: bit-identical outputs) and distributionally in TRAIN mode
(same mean→crop→mirror→scale order, per-sample uniform offsets and
mirror coin; the RNG is a JAX key rather than numpy, so draws differ).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparknet_tpu.data.transform import TransformConfig


# device scope of the augment's ops; not ``L.``: a trace reader books
# ``L.<name>`` as a net layer
AUGMENT_SCOPE = "S.augment"


# the one pass (:func:`crop_mirror`).  On the chip a batch of f32 crops
# [n, ch, crop, crop] lies BATCH-MINOR: n along a vreg's 128 lanes, the
# crop's columns along its sublanes (XLA's layout {0,3,2,1:T(8,128)} for
# that shape; the uint8 batch arrives batch-major).  So a crop is also a
# transposition of 128 images, and the kernel works on groups of LANES
LANES = 128
SUBLANES = 8
OUT_ROWS = 8  # crop rows one grid step transposes and writes
UNROLL = 4  # images a loop step selects: independent chains to interleave
VMEM_CAP = 96 << 20  # what a group may take of a v5e core's 128 MiB


def _crop_group(ho_ref, wo_ref, flip_ref, mean_ref, x_ref, o_ref, s_ref, *,
                crop, rows, scale):
    """One grid step (group, channel, row block).  ``x_ref``: the
    group's uint8 planes [LANES, 1, h, w], the same block for every row
    block; ``o_ref``: [1, OUT_ROWS, crop, LANES] of the [ch, crop, crop,
    n] result; ``s_ref``: the group's cropped planes, f32
    [w / LANES, LANES · rows (+ OUT_ROWS), LANES], image ``n``'s row
    ``i`` at ``n · rows + i``, one slab per 128 columns (a strided load
    wants a 128-wide base)."""
    group, k, block = (pl.program_id(a) for a in range(3))
    h, w = x_ref.shape[2:]

    @pl.when(block == 0)
    def _():
        # per image: columns by a one-hot [w, w] product on the MXU (the
        # mirror is the selector read backwards; 0..255 and 0 / 1 are
        # exact in bf16, every output is one product plus zeros), rows by
        # a sublane rotate (Mosaic loads no row range from a sublane it
        # learns at run time)
        src = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)

        def one(n):
            i = group * LANES + n
            ho, wo, flip = ho_ref[i], wo_ref[i], flip_ref[i]
            pick = src == wo + jnp.where(flip > 0, crop - 1 - col, col)
            plane = x_ref[n, 0].astype(jnp.int32).astype(jnp.float32)
            z = jnp.dot(plane.astype(jnp.bfloat16),
                        pick.astype(jnp.float32).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
            z = pltpu.roll(z, jnp.where(ho > 0, h - ho, 0), 0)[:rows]
            at = pl.ds(pl.multiple_of(n * rows, SUBLANES), rows)
            for slab in range(w // LANES):
                s_ref[slab, at, :] = z[:, slab * LANES:(slab + 1) * LANES]

        def several(t, carry):
            # unrolled by hand (Mosaic's fori_loop unrolls all or nothing):
            # the product's latency hides behind the next image's
            for u in range(UNROLL):
                one(t * UNROLL + u)
            return carry

        jax.lax.fori_loop(0, LANES // UNROLL, several, 0)

    mean = mean_ref[k]
    for r in range(OUT_ROWS):
        i = block * OUT_ROWS + r
        for slab in range(pl.cdiv(crop, LANES)):
            # row i of all 128 images [n, 128 columns] -> [columns, n]
            out = s_ref[slab, pl.ds(i, LANES, stride=rows), :].T - mean
            if scale != 1.0:
                out = out * scale
            cols = min(crop - slab * LANES, LANES)
            o_ref[0, r, slab * LANES:slab * LANES + cols, :] = out[:cols]


def _kept_rows(crop: int) -> int:
    """Rows of a cropped plane as the kernel keeps it: whole sublanes."""
    return -(-crop // SUBLANES) * SUBLANES


def _vmem_bytes(h: int, w: int, crop: int) -> int:
    """What :func:`crop_mirror` holds in VMEM: the group's cropped
    planes, and two blocks each of its uint8 planes and its result."""
    rows = _kept_rows(crop)
    return (4 * w * (LANES * rows + OUT_ROWS) + 2 * LANES * h * w
            + 2 * 4 * OUT_ROWS * rows * LANES)


def crop_tiles(n: int, h: int, w: int, crop: int) -> bool:
    """Whether :func:`crop_mirror` takes a [n, ch, h, w] uint8 batch:
    whole groups of 128 images, planes of whole uint8 tiles, a group
    that fits VMEM."""
    return (n % LANES == 0 and w % LANES == 0 and h % 32 == 0
            and 0 < crop <= min(h, w) and _vmem_bytes(h, w, crop) <= VMEM_CAP)


def crop_mirror(x, hos, wos, flip, mean, *, crop: int, scale: float = 1.0,
                interpret: bool = False):
    """``(x[n, :, ho:ho+crop, wo:wo+crop] (mirrored where flip) - mean) ·
    scale`` as f32 [n, ch, crop, crop] from a uint8 [n, ch, h, w] batch
    (``crop_tiles``), per-image ``hos`` / ``wos`` / ``flip`` [n] and a
    per-channel ``mean`` [ch]: one Pallas kernel that reads the batch
    once and writes the crop once, bit-equal to the same steps in f32."""
    n, ch, h, w = x.shape
    rows = _kept_rows(crop)
    out = pl.pallas_call(
        functools.partial(_crop_group, crop=crop, rows=rows, scale=scale),
        out_shape=jax.ShapeDtypeStruct((ch, crop, crop, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // LANES, ch, pl.cdiv(crop, OUT_ROWS)),
            in_specs=[pl.BlockSpec((LANES, 1, h, w),
                                   lambda g, k, b, *_: (g, k, 0, 0))],
            out_specs=pl.BlockSpec((1, OUT_ROWS, crop, LANES),
                                   lambda g, k, b, *_: (k, b, 0, g)),
            # OUT_ROWS more: the last row block's strided loads run past
            # the crop's rows (into rows nothing keeps)
            scratch_shapes=[pltpu.VMEM(
                (w // LANES, LANES * rows + OUT_ROWS, LANES), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(h, w, crop) + (16 << 20)),
        interpret=interpret, name="augment_crop_mirror",
    )(hos.astype(jnp.int32), wos.astype(jnp.int32), flip.astype(jnp.int32),
      mean.astype(jnp.float32), x)
    # batch-minor as stored: under the result's layout on the chip this
    # transposition is a bitcast
    return out.transpose(3, 0, 1, 2)


def _on_one_tpu(images) -> bool:
    """A placed array, whole on one TPU chip (the kernel is one chip's
    program; a host array or a sharded one takes ``_augment``)."""
    devices = images.devices() if isinstance(images, jax.Array) else ()
    return len(devices) == 1 and next(iter(devices)).platform == "tpu"


class DeviceAugment:
    """jit-compatible batch transform: uint8/float device array + PRNG
    key → float32 crops, in the INTERNAL layout (``Config.layout``,
    ``ops/layout.py``): (N, C, H, W) → (N, C, crop, crop) under nchw,
    (N, H, W, C) → (N, crop, crop, C) under nhwc.

    The nhwc path is where the data-formatting story closes end to end:
    image bytes arrive HWC off the wire (JPEG decoders, the record DB,
    ``data/minibatch.py``'s packers all see HWC first), so shipping
    (N, H, W, C) uint8 is the feed link's NATURAL orientation — zero
    entry transpose on either side of the link, and the augment feeds
    a step whose convs already run channels-last.

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
        # trainer's aug4/aug5, device_fn's one pass), so a trace names
        # the augment's device time.  An eager caller (device_fn's
        # fallback) dispatches op by op and carries none: jax resets the
        # name stack for an eager primitive
        with jax.named_scope(AUGMENT_SCOPE):
            return self._augment(images, key, train)

    def takes_fused(self, images) -> bool:
        """Whether a batch takes the one pass (:func:`crop_mirror`), by
        what it is: uint8, NCHW, whole on one TPU chip, in shapes that
        tile, under a crop and a per-channel mean or none.  A per-pixel
        ``mean_image`` (it would have to be cropped per sample too), float
        input, no crop, NHWC (three channels on the lanes: no cell ships
        it) and every other backend take ``_augment``."""
        if (self.layout != "nchw" or self._mean is not None
                or not self.config.crop_size or jnp.ndim(images) != 4
                or images.dtype != jnp.uint8 or not _on_one_tpu(images)):
            return False
        n, _, h, w = images.shape
        return crop_tiles(n, h, w, self.config.crop_size)

    def fused(self, images, key, train: bool = True):
        """``_augment`` of a batch that :meth:`takes_fused`, as one pass:
        the same draws from ``key`` (the same calls in the same order),
        then :func:`crop_mirror`.  Call it under ``jax.jit``."""
        cfg = self.config
        n, ch, h, w = images.shape
        c = cfg.crop_size
        k_h, k_w, k_flip = jax.random.split(key, 3)
        if train:
            hos = jax.random.randint(k_h, (n,), 0, h - c + 1)
            wos = jax.random.randint(k_w, (n,), 0, w - c + 1)
        else:
            hos = jnp.full((n,), (h - c) // 2)
            wos = jnp.full((n,), (w - c) // 2)
        if train and cfg.mirror:
            flip = jax.random.bernoulli(k_flip, 0.5, (n,))
        else:
            flip = jnp.zeros((n,), bool)
        mean = jnp.broadcast_to(
            jnp.asarray(cfg.mean_value or 0.0, jnp.float32), (ch,))
        with jax.named_scope(AUGMENT_SCOPE):
            return crop_mirror(images, hos, wos, flip, mean, crop=c,
                               scale=cfg.scale)

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
        base_key = jax.random.key(1234 + pid + (seed or 0))

        # the key is an argument, not a constant of the program: one
        # executable serves every seed, so the persistent cache keeps it
        @jax.jit
        def one_pass(x, key, it):
            return self.fused(x, jax.random.fold_in(key, it))

        def fn(feeds, it):
            x = feeds[key_name]
            out = (one_pass(x, base_key, it) if self.takes_fused(x) else
                   self(x, jax.random.fold_in(base_key, it)))
            return {**feeds, key_name: out}

        # for the caller's ``sn.feed.augment`` span: 1 where the batch
        # takes the one pass, 0 where it falls back
        fn.fused = lambda feeds: int(self.takes_fused(feeds[key_name]))
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
