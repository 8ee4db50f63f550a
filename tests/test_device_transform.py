"""DeviceAugment: the on-device (XLA) twin of the host DataTransformer.

TEST mode must be bit-identical to the host path; TRAIN mode must draw
from exactly the space of valid (offset, flip) crops with the same
mean→crop→mirror→scale order (ref: data_transformer.cpp:19-119).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.data.device_transform import DeviceAugment
from sparknet_tpu.data.prefetch import DevicePrefetcher
from sparknet_tpu.data.transform import DataTransformer, TransformConfig


@pytest.fixture
def u8_batch(rng):
    return (rng.rand(6, 3, 12, 10) * 255).astype(np.uint8)


def test_test_mode_matches_host_exactly(u8_batch, rng):
    mean = rng.rand(3, 12, 10).astype(np.float32) * 100
    cfg = TransformConfig(crop_size=8, mirror=True, mean_image=mean, scale=0.5)
    host = DataTransformer(cfg)(u8_batch, train=False)
    dev = DeviceAugment(cfg)(jnp.asarray(u8_batch), jax.random.key(0),
                             train=False)
    np.testing.assert_allclose(np.asarray(dev), host, atol=1e-5, rtol=1e-6)


def test_test_mode_mean_value(u8_batch):
    cfg = TransformConfig(crop_size=6, mean_value=(10.0, 20.0, 30.0))
    host = DataTransformer(cfg)(u8_batch, train=False)
    dev = DeviceAugment(cfg)(jnp.asarray(u8_batch), jax.random.key(1),
                             train=False)
    np.testing.assert_allclose(np.asarray(dev), host, atol=1e-5, rtol=1e-6)


def test_train_outputs_are_valid_crops(rng):
    """Every TRAIN sample must equal some (offset, flip) window of the
    mean-subtracted input — the exact candidate space of the host path."""
    x = (rng.rand(8, 2, 6, 7) * 255).astype(np.uint8)
    cfg = TransformConfig(crop_size=4, mirror=True)
    out = np.asarray(DeviceAugment(cfg)(jnp.asarray(x), jax.random.key(7)))
    xf = x.astype(np.float32)
    for i in range(len(x)):
        candidates = []
        for ho in range(6 - 4 + 1):
            for wo in range(7 - 4 + 1):
                win = xf[i, :, ho : ho + 4, wo : wo + 4]
                candidates.append(win)
                candidates.append(win[:, :, ::-1])
        assert any(np.allclose(out[i], w, atol=1e-4) for w in candidates), i


def test_mirror_statistics_and_correctness(rng):
    x = (rng.rand(512, 1, 4, 4) * 255).astype(np.uint8)
    cfg = TransformConfig(mirror=True)
    out = np.asarray(DeviceAugment(cfg)(jnp.asarray(x), jax.random.key(3)))
    xf = x.astype(np.float32)
    flipped = np.array(
        [not np.allclose(out[i], xf[i]) for i in range(len(x))]
    )
    assert 0.3 < flipped.mean() < 0.7  # fair coin
    for i in np.where(flipped)[0][:16]:
        np.testing.assert_allclose(out[i], xf[i, :, :, ::-1], atol=1e-5)


def test_jit_and_dtype(u8_batch):
    cfg = TransformConfig(crop_size=8, mirror=True)
    aug = DeviceAugment(cfg)
    f = jax.jit(lambda x, k: aug(x, k, train=True))
    y = f(jnp.asarray(u8_batch), jax.random.key(0))
    assert y.shape == (6, 3, 8, 8) and y.dtype == jnp.float32


def test_rejects_native_backend_and_double_mean(rng):
    with pytest.raises(ValueError, match="backend"):
        DeviceAugment(TransformConfig(backend="native"))
    with pytest.raises(ValueError, match="not both"):
        DeviceAugment(TransformConfig(mean_value=(1.0,),
                                      mean_image=np.zeros((1, 2, 2), np.float32)))


def test_prefetcher_device_fn_integration(rng):
    """uint8 host batches -> device_put -> DeviceAugment in the worker."""
    batches = [(rng.rand(4, 3, 10, 10) * 255).astype(np.uint8)
               for _ in range(3)]
    aug = DeviceAugment(TransformConfig(crop_size=8, mirror=True))
    fetcher = DevicePrefetcher(
        lambda it: {"data": batches[it]},
        num_iters=3,
        device_fn=lambda feeds, it: {
            "data": aug(feeds["data"], jax.random.key(it))
        },
    )
    with fetcher:
        got = list(fetcher)
    assert len(got) == 3
    for feeds in got:
        assert feeds["data"].shape == (4, 3, 8, 8)
        assert feeds["data"].dtype == jnp.float32


# -- the solo feed's one pass (PR 39) ---------------------------------------

from sparknet_tpu.data import device_transform  # noqa: E402
from sparknet_tpu.obs.recorder import flight  # noqa: E402
from sparknet_tpu.obs.sentinel import get_sentinel  # noqa: E402

MEANS = {"mean_value": (104.0, 117.0, 123.0), "none": ()}


def _nchw_batch(n, size, layout="nchw", seed=0):
    x = np.random.RandomState(seed).randint(0, 256, (n, 3, size, size))
    x = x.astype(np.uint8)
    return jnp.asarray(x.transpose(0, 2, 3, 1) if layout == "nhwc" else x)


@pytest.fixture
def as_on_a_chip(monkeypatch):
    """The CPU's arrays pass for a TPU's and the kernel runs in Pallas's
    interpreter, on any thread: what a test needs to walk ``device_fn``'s
    TPU branch here (no option of the program offers it)."""
    monkeypatch.setattr(device_transform, "_on_one_tpu",
                        lambda images: isinstance(images, jax.Array))
    monkeypatch.setattr(device_transform, "crop_mirror", functools.partial(
        device_transform.crop_mirror, interpret=True))


@pytest.mark.parametrize("train", [True, False], ids=["train", "test"])
@pytest.mark.parametrize("mean", ["mean_value", "none"])
@pytest.mark.parametrize("mirror", [True, False], ids=["mirror", "plain"])
@pytest.mark.parametrize("size, crop", [(256, 227), (256, 224), (128, 100)],
                         ids=["227of256", "224of256", "100of128"])
def test_one_pass_equals_augment_bit_for_bit(size, crop, mirror, mean, train,
                                             as_on_a_chip):
    """``fused`` against the oracle on one group of 128 images: the same
    draws (offsets and flips decide every output) and the same f32
    values, in Pallas's interpreter."""
    cfg = TransformConfig(crop_size=crop, mirror=mirror,
                          mean_value=MEANS[mean], scale=0.5 if crop == 100
                          else 1.0)
    aug = DeviceAugment(cfg, layout="nchw")
    x, key = _nchw_batch(128, size), jax.random.key(size + crop)
    assert aug.takes_fused(x)
    got = aug.fused(x, key, train)
    assert got.dtype == jnp.float32 and got.shape == (128, 3, crop, crop)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(aug._augment(x, key, train)))


def _span_stats(name):
    return [span[4] for span in flight()[0] if span[0] == name]


def _fed(device_fn, batches):
    """The batches ``device_fn`` hands on through a prefetcher, and the
    ``fused`` stats of its ``sn.feed.augment`` spans."""
    before = len(_span_stats("sn.feed.augment"))
    with DevicePrefetcher(lambda it: {"data": batches[it]},
                          num_iters=len(batches),
                          device_fn=device_fn) as fetcher:
        got = [feeds["data"] for feeds in fetcher]
    return got, [c.get("fused") for c in
                 _span_stats("sn.feed.augment")[before:]]


def test_device_fn_is_one_jitted_call_a_batch(as_on_a_chip):
    """Where the batch tiles, ``device_fn`` dispatches the one pass:
    equal to ``_augment`` under the adapter's key policy, ``fused`` = 1 on
    the worker's span, and nothing compiles after the first batch of a
    geometry."""
    aug = DeviceAugment(TransformConfig(crop_size=100, mirror=True,
                                        mean_value=MEANS["mean_value"]),
                        layout="nchw")
    batches = [np.asarray(_nchw_batch(128, 128, seed=s)) for s in range(3)]
    fn = aug.device_fn(pid=2, seed=5)
    first = fn({"data": jnp.asarray(batches[0])}, 0)["data"]
    sentinel = get_sentinel().install()
    compiled = sentinel.count
    got, fused = _fed(aug.device_fn(pid=2, seed=5), batches)
    assert fused == [1, 1, 1]
    base = jax.random.key(1234 + 2 + 5)
    for it, (x, y) in enumerate(zip(batches, got)):
        np.testing.assert_array_equal(np.asarray(y), np.asarray(
            aug._augment(jnp.asarray(x), jax.random.fold_in(base, it), True)))
    np.testing.assert_array_equal(np.asarray(first), np.asarray(got[0]))
    # a fresh adapter compiles its own pass once (batch 0), then nothing
    again = sentinel.count
    fn({"data": jnp.asarray(batches[1])}, 1)
    fn({"data": jnp.asarray(batches[2])}, 2)
    assert sentinel.count == again and again > compiled


@pytest.mark.parametrize("why", ["mean_image", "float_input", "no_crop",
                                 "nhwc", "ragged_batch", "cpu"])
def test_fallbacks_take_augment_and_say_so(why, request):
    """What the one pass does not take goes through ``_augment`` as it
    always did, and the span reports ``fused`` = 0."""
    if why != "cpu":
        request.getfixturevalue("as_on_a_chip")
    layout = "nhwc" if why == "nhwc" else "nchw"
    n = 100 if why == "ragged_batch" else 128
    cfg = TransformConfig(
        crop_size=0 if why == "no_crop" else 100, mirror=True,
        mean_image=(np.full((3, 128, 128), 7.0, np.float32)
                    if why == "mean_image" else None),
        mean_value=() if why == "mean_image" else MEANS["mean_value"])
    aug = DeviceAugment(cfg, layout=layout)
    x = np.asarray(_nchw_batch(n, 128, layout))
    if why == "float_input":
        x = x.astype(np.float32)
    assert not aug.takes_fused(jnp.asarray(x))
    got, fused = _fed(aug.device_fn(), [x])
    assert fused == [0]
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(aug._augment(
        jnp.asarray(x), jax.random.fold_in(jax.random.key(1234), 0), True)))


def test_another_device_fn_carries_no_fused_stat():
    _, fused = _fed(lambda feeds, it: feeds, [np.zeros((2, 3), np.uint8)])
    assert fused == [None]


@pytest.mark.parametrize("rank", [4, 5])
def test_the_trainer_adapter_lowers_as_before(rank):
    """``trainer_device_fn`` keeps its jitted ``_augment`` (``aug4`` /
    ``aug5``) until the trainer cell can use a faster augment (ROADMAP
    S11): its StableHLO is that of a twin written out here as the
    adapter stood before PR 39, the gather still in it and no product."""
    aug = DeviceAugment(TransformConfig(crop_size=100, mirror=True,
                                        mean_value=MEANS["mean_value"]),
                        layout="nchw")
    x = jnp.zeros(((2,) if rank == 5 else ()) + (128, 3, 128, 128), jnp.uint8)
    fn = aug.trainer_device_fn(pid=1, seed=2)
    base = jax.random.key(1234 + 1 + 2)

    @jax.jit
    def aug4(x, key):
        return aug(x, key)

    @jax.jit
    def aug5(x, key):
        keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(
            jnp.arange(x.shape[0]))
        return jax.vmap(lambda xs, ks: aug(xs, ks))(x, keys)

    def adapter(x):
        return fn({"data": x}, 3)["data"]

    def twin(x):
        return (aug5 if rank == 5 else aug4)(x, jax.random.fold_in(base, 3))

    text = jax.jit(adapter).lower(x).as_text()
    assert text == jax.jit(twin).lower(x).as_text().replace("twin", "adapter")
    assert "stablehlo.gather" in text or "dynamic_slice" in text
    assert "dot_general" not in text and "custom_call" not in text
