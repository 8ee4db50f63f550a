"""The feed, from a ``--data`` spec to the host batches a solver or a
trainer is handed: the ``Feed`` type, one opener per source kind, and the
one place a spec is parsed.

A source kind (``proto``, ``cifar:``, ``db:``, ``tokens:``, ``synthetic``;
``cli.py``'s docstring says what each reads) is one opener and one entry
of ``OPENERS``.  The openers take plain values by name, not the CLI's
flags, and exit (``SystemExit``) on what the user got wrong, as the
CLI they were moved from did: a debt (ROADMAP.md, D16).  ``rounds`` turns
a feed into the trainer's tau-rounds; ``prefetch`` places it ahead of the
solver.
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np

from sparknet_tpu.data.prefetch import fresh_bytes
from sparknet_tpu.data.rounds import Turns
from sparknet_tpu.obs import get_recorder
from sparknet_tpu.obs.recorder import Span


class Feed:
    """What a train feed is: ``feed(it)`` reads host batch ``it``, a dict
    of ``[batch, ...]`` arrays per feed key, and carries what its
    consumers ask of it.  ``takes_out``: ``feed(it, out=arrays)`` writes
    the batch into the arrays of ``out`` (one per feed key) when it can,
    so the consumer that owns them can reuse them; the batch it returns
    says where the data landed.  ``device_fn(feeds, it)``: the in-XLA
    transform the async feed dispatches on placed batches;
    ``trainer_device_fn`` its twin for ``ParallelTrainer.feed_device_fn``,
    applied after the trainer's own placement.  ``pipeline_factory(
    num_batches, start_index, workers)``: the source again as a
    ``ProcessPipeline``, for ``Config.feed == "process"``.  ``lock``:
    calls into the feed are made under it, in turn (``rounds.Turns``): a
    feed drives one cursor, and two round feeds over it may each have a
    thread inside.  A plain function is a valid data fn wherever a feed
    is: consumers read the fields with ``getattr`` and these defaults,
    and its lock with ``rounds.lock_of``."""

    FIELDS = ("takes_out", "device_fn", "trainer_device_fn",
              "pipeline_factory")

    def __init__(self, read, *, takes_out=False, device_fn=None,
                 trainer_device_fn=None, pipeline_factory=None, lock=None):
        self._read = read
        self.takes_out = takes_out
        self.device_fn = device_fn
        self.trainer_device_fn = trainer_device_fn
        self.pipeline_factory = pipeline_factory
        self.lock = lock or Turns()

    def __call__(self, it, out=None):
        return self._read(it) if out is None else self._read(it, out=out)

    def wrap(self, read, **changed) -> Feed:
        """The same feed around another read function: every field and
        the SAME lock, but for what ``changed`` names."""
        fields = {f: getattr(self, f) for f in self.FIELDS}
        return Feed(read, lock=self.lock, **{**fields, **changed})


def parse_spec(spec, net=None) -> tuple[str, str]:
    """THE parse of a ``--data`` spec: its kind (a key of ``OPENERS``, ""
    for none) and its text with ``auto`` (the CLI's default) resolved
    against ``net``: a net whose own data layers are self-describing
    streams them, ``caffe train --solver=x`` semantics, otherwise
    synthetic batches (zoo/RDD nets, where smoke runs feed random data by
    design).  Declaration check only (no file I/O): the proto opener
    builds the source and raises the loud cannot-stream error for
    unreadable declared sources."""
    text = spec or ""
    if text == "auto" and net is not None:
        from sparknet_tpu.data.listfile import _SOURCES

        text = ("proto" if any(l.type in _SOURCES for l in net.input_layers)
                else "synthetic")
    return next((k for k in OPENERS if
                 (text.startswith(k) if k.endswith(":") else text == k)),
                ""), text


def spec_paths(text, kind, pid, nproc=1) -> tuple[str, str, int, int]:
    """(train path, test path, stride, offset) of ``<kind>train[,test]``.
    {proc} expands to ``pid`` in the train path; the eval stream is the
    same on every process (only training shards): every host then
    computes the same score, keeping the sum-then-normalize semantics
    well-defined.  One shared train file across a multi-process job is
    sharded by batch interleave (process p takes batches p, p+n, ...):
    correct, but every host reads everything; the {proc} per-worker
    layout is the efficient path."""
    paths = text[len(kind):].split(",")
    shared = "{proc}" not in paths[0] and nproc > 1
    return (paths[0].replace("{proc}", str(pid)),
            paths[min(1, len(paths) - 1)].replace("{proc}", "0"),
            *((nproc, pid) if shared else (1, 0)))


def db_peek_shapes(spec, net, pid) -> dict:
    """Shapes for ``Data``-layer tops peeked from the user's ``--data db:``
    path — Caffe parity (geometry comes from the DB, data_layer.cpp:40-48)
    with the streamed DB standing in for a ``data_param.source`` that isn't
    on this machine.  Empty dict when nothing needs peeking.  {proc}
    expands to ``pid``, THIS process: in the per-worker-DB layout a host
    may hold only its own shard."""
    kind, text = parse_spec(spec)
    if kind != "db:":
        return {}
    known = net.feed_shapes()
    missing = [
        l for l in net.input_layers
        if getattr(l, "TYPE", "") == "Data"
        and any(t not in known for t in l.tops)
    ]
    if not missing:
        return {}
    from sparknet_tpu.data.createdb import peek_db_shape

    path = spec_paths(text, kind, pid)[0]
    try:
        chw = peek_db_shape(path)
    except (OSError, ValueError) as e:
        raise SystemExit(f"--data db: {path}: {e}") from None
    out = {}
    for l in missing:
        shapes = l.shapes_for_chw(chw)
        if shapes:
            out.update(zip(l.tops, shapes))
    return out


def feed_shapes(net, spec, pid) -> dict:
    shapes = net.feed_shapes()
    shapes.update(db_peek_shapes(spec, net, pid))
    if not shapes:
        raise SystemExit(
            "net declares no input shapes; use RDD/Input layers, keep the "
            "DB at data_param.source on disk, or stream one with --data "
            "db:<path> (a Data layer's geometry comes from its DB — ref: "
            "data_layer.cpp DataLayerSetUp)"
        )
    return shapes


def internalize(fn):
    """Wrap a data fn so canonical-NCHW host batches (cifar readers, DB
    cursors, listfile sources — every real data plane emits blob order)
    arrive in the INTERNAL layout (``Config.layout``, ops/layout.py).
    A passthrough under nchw; a ``Feed`` keeps its fields: its
    ``device_fn`` (whose DeviceAugment already speaks the internal
    layout) and ``pipeline_factory`` (whose sources produce the internal
    layout NATIVELY — the process feed never pays this per-batch
    transpose, which is the wire half of the nhwc zero-transpose
    contract).  A destination (``takes_out``) is internal too: the cursor
    is handed its canonical view and fills it through the strides."""
    from sparknet_tpu.ops.layout import (
        feeds_to_internal,
        from_internal,
        is_nhwc,
    )

    if fn is None or not is_nhwc():
        return fn

    def read(it, out=None):
        if out is None:
            return feeds_to_internal(fn(it))
        return feeds_to_internal(fn(it, out={
            k: from_internal(v, "nhwc") for k, v in out.items()}))

    return fn.wrap(read) if isinstance(fn, Feed) else read


def read_span(fn, images, **counts) -> Feed:
    """``sn.feed.read`` around a feed: one span per host batch,
    from the cursor to the decoded, collated, cast and internalized
    batch, on whichever thread asks for it (the DevicePrefetcher's feed
    thread in the solo loop, ``rounds.stack_tau``'s in the trainer's).
    ``alloc_bytes``: what of the batch lies in newly allocated arrays,
    0 when it all went into the caller's ``out``.  ``images`` counts the
    batch's records (sequences for a ``tokens:`` source, whose ``counts``
    add ``tokens``)."""

    def read(it, out=None):
        with get_recorder().span("sn.feed.read", host=True, it=it,
                                 images=images, **counts) as span:
            feeds = fn(it, out=out)
            span.set(alloc_bytes=fresh_bytes(feeds, out))
            return feeds

    return fn.wrap(read)


def attach_device_augment(feed: Feed, cfg, pid, seed=None) -> None:
    """The in-XLA transform as the async feed's ``device_fn`` — the key
    policy lives in :meth:`DeviceAugment.device_fn`, shared by the
    threaded prefetcher and the process pipeline's device stage — plus
    the trainer-path twin (``trainer_device_fn``): the hook
    ``ParallelTrainer``/``ElasticTrainer`` apply after their own feed
    placement, so the uint8 wire reaches the chip on the tau path too."""
    from sparknet_tpu.data.device_transform import DeviceAugment

    try:
        aug = DeviceAugment(cfg)
    except ValueError as e:
        raise SystemExit(f"transform_param: {e}") from None
    feed.device_fn = aug.device_fn(pid, seed)
    feed.trainer_device_fn = aug.trainer_device_fn(pid, seed)


def device_augment(augment, trainer, prefetch) -> bool:
    """``augment == "device"``, with its preconditions checked.  The
    trainer path needs NO async-feed precondition: the trainer owns its
    own feed placement and applies the augment post-placement
    (``trainer_device_fn`` -> ``ParallelTrainer.feed_device_fn``), so
    uint8 wire batches work with the threaded AND process feeds alike.
    Only the solo step loop requires an async device stage to dispatch
    the augment on."""
    if augment != "device":
        return False
    from sparknet_tpu.common import get_config

    if not trainer and prefetch <= 0 and get_config().feed != "process":
        raise SystemExit(
            "--augment device rides the async feed: pass --prefetch N "
            "or --feed process (the DeviceAugment dispatch belongs on "
            "the feed's device stage, not the step loop)")
    return True


def open_feeds(spec, net, test_net=None, *, pid=0, nproc=1, seed=None,
               augment="host", solver_path="", data_scale=0.0, prefetch=0,
               trainer=False):
    """(train feed, test fn) for a ``--data`` spec: the opener of its
    kind, called with these plain values by name (and ``text``, the spec
    resolved; ``data_shape``, the net's ``data`` blob in the internal
    layout, not for ``proto``, whose sources define their own geometry;
    ``host_seed``; ``was_auto``).  An opener names the ones it reads.

    ``test_net``: when the caller holds a distinct TEST-phase net whose
    own Data layer declares transform_param (crop/mean/scale), the test
    stream honors THOSE params — the reference transforms each phase with
    its own declaration (ref: data_transformer.cpp + net.cpp phase
    filtering); without it the train net's params cover both phases.

    In a multi-process job each process streams DIFFERENT data (its own
    partition, ref: CifarApp.scala:118-130 per-executor RDD partitions):
    batch indices interleave by ``pid`` of ``nproc`` and the random
    streams seed per process.  ``trainer``: a trainer owns the placement
    (tau > 1, sync-SGD, elastic)."""
    kind, text = parse_spec(spec, net)
    if augment == "device" and kind not in ("cifar:", "db:"):
        raise SystemExit(
            "--augment device is wired to the cifar: and db: sources "
            "(other sources transform on the host)")
    # sn.feed.open: one per spec opened (files, indexes, the augment's
    # programs); the batches it then reads are sn.feed.read's
    with Span(None, "sn.feed.open", host=True, compile_stats=True,
              source=kind.rstrip(":") or "unknown"):
        data_shape = ()
        if kind != "proto":
            data_shape = feed_shapes(net, text, pid)["data"]
        if not kind:
            raise SystemExit(f"unknown --data source {text!r}")
        return OPENERS[kind](
            text=text, net=net, test_net=test_net, data_shape=data_shape,
            pid=pid, nproc=nproc, seed=seed,
            host_seed=1234 + pid + (seed or 0), was_auto=spec == "auto",
            augment=augment, solver_path=solver_path, data_scale=data_scale,
            prefetch=prefetch, trainer=trainer)


def _open_proto(net, test_net, pid, nproc, host_seed, solver_path, was_auto,
                **_):
    """The net's OWN data-layer params drive the host stream — a
    reference Data/ImageData/WindowData/HDF5Data prototxt trains end to
    end with no surgery (ref: data_layer.cpp, image_data_layer.cpp,
    window_data_layer.cpp, hdf5_data_layer.cpp read these sources inside
    the layer; here the host reader replaces the layer's prefetch
    thread)."""
    from sparknet_tpu.data.listfile import source_from_net

    try:
        train_src = source_from_net(net, seed=host_seed, anchor=solver_path)
    except (OSError, ValueError, LookupError) as e:
        mode = "auto" if was_auto else "proto"
        # never silently substitute random data for a declared source — a
        # garbage model trained without error is the worst outcome
        raise SystemExit(
            f"--data {mode}: the net's data layer declares a source "
            f"that cannot stream ({e}); pass --data db:<path> / "
            "cifar:<dir> to point at the data, or --data synthetic "
            "to smoke-run on random batches"
        ) from None

    # Eval fallback: a SEPARATE lazily-built instance with a fixed seed so
    # every process scores the identical stream (the cifar/db paths'
    # sum-then-normalize invariant) and eval cadence can't advance the
    # training stream.  Lazy because the usual train_val case replaces it
    # with the TEST net's own source, below — re-parsing a large window
    # file for a throwaway would be waste.
    @functools.cache
    def eval_source():
        try:
            return source_from_net(net, seed=4321, anchor=solver_path)
        except (OSError, ValueError, LookupError) as e:
            raise SystemExit(f"--data proto (eval): {e}") from None

    if nproc > 1:
        # sequential (unshuffled) sources would otherwise stream the SAME
        # lines on every process; interleave batches by process id like
        # the shared-db path (every host decodes everything — correct, if
        # not maximally efficient)
        inner, state = train_src, {"started": False}

        def train_src(it):  # noqa: F811 — deliberate shadowing wrapper
            skip = pid if not state["started"] else nproc - 1
            state["started"] = True
            for _ in range(skip):
                inner(it)
            return inner(it)

    test_fn = internalize(lambda b: eval_source()(b))
    if test_net is not None:
        # the TEST net's data layer names its own source file + phase; a
        # train-only prototxt (no TEST-phase listfile layer) keeps the
        # train net's stream for any eval
        try:
            test_fn = source_from_net(test_net, seed=4321, anchor=solver_path)
        except LookupError:
            pass
        except (OSError, ValueError) as e:
            raise SystemExit(f"--data proto (test net): {e}") from None
    return internalize(Feed(train_src)), test_fn


def _open_cifar(text, data_shape, pid, nproc, seed, augment, trainer,
                prefetch, **_):
    from sparknet_tpu.data.cifar import CifarLoader
    from sparknet_tpu.data.transform import DataTransformer, TransformConfig

    batch = data_shape[0]
    loader = CifarLoader(text[len("cifar:"):])
    xform_cfg = TransformConfig(mean_image=loader.mean_image)
    xform = DataTransformer(xform_cfg)
    xtr, ytr = loader.train_images, loader.train_labels
    xte, yte = loader.test_images, loader.test_labels

    if batch > len(ytr) or batch > len(yte):
        raise SystemExit(
            f"--batch {batch} exceeds dataset size {min(len(ytr), len(yte))}")

    def walk(images, labels, index, train=None):
        """Batch ``index`` of the modulo walk over a split, raw, or
        host-transformed for the phase ``train`` says."""
        lo = (index * batch) % (len(labels) - batch + 1)
        data = images[lo : lo + batch]
        return {"data": data if train is None else xform(data, train),
                "label": labels[lo : lo + batch].astype(np.int32)}

    # with a device augment the train stream ships raw uint8 over the
    # feed link and the mean-subtract runs in-graph, via DeviceAugment in
    # the prefetcher's device_fn (4x fewer host->HBM bytes than f32 feeds)
    device_aug = device_augment(augment, trainer, prefetch)

    def pipeline_factory(num_batches, start_index=0, workers=None):
        """Process-feed twin of the threaded cifar stream: raw batch slices
        are index-pure (same modulo walk as the thread path), the host
        transform — when any — runs IN the workers, and the wire is
        reoriented ONCE at source build under nhwc (the per-batch
        ``internalize`` transpose never happens)."""
        from sparknet_tpu.data.pipeline import (
            DataFnSource,
            ProcessPipeline,
            TransformStage,
        )
        from sparknet_tpu.ops.layout import is_nhwc

        lay = "nhwc" if is_nhwc() else "nchw"
        xs = (np.ascontiguousarray(xtr.transpose(0, 2, 3, 1))
              if lay == "nhwc" else xtr)
        stage = (None if device_aug else
                 TransformStage(xform_cfg, train=True, layout=lay))
        return ProcessPipeline(
            DataFnSource(lambda it: walk(xs, ytr, it * nproc + pid)),
            stage, num_batches=num_batches, start_index=start_index,
            workers=workers, name="feed.cifar")

    train = Feed(lambda it: walk(xtr, ytr, it * nproc + pid,
                                 None if device_aug else True),
                 pipeline_factory=pipeline_factory)
    if device_aug:
        attach_device_augment(train, xform_cfg, pid, seed=seed)
    # the eval walk is the same on every process (``spec_paths``)
    return (internalize(train),
            internalize(lambda b: walk(xte, yte, b, False)))


def _open_db(text, net, test_net, data_shape, pid, nproc, seed, host_seed,
             solver_path, data_scale, augment, trainer, prefetch, **_):
    """DB-backed training — the CifarDBApp/ImageNetRunDBApp flow (ref:
    src/main/scala/apps/CifarDBApp.scala:96-131 reads per-worker LevelDBs
    through Caffe's DataLayer).  Accepts the native RecordDB or a real
    Caffe LMDB (auto-detected); "db:train[,test]" with "{proc}"
    substituted by process id for the reference's per-worker-DB layout."""
    from sparknet_tpu.data.createdb import db_minibatches, peek_db_shape
    from sparknet_tpu.data.records import probe_record_backend
    from sparknet_tpu.data.transform import (
        DataTransformer,
        TransformConfig,
        load_mean_file,
        resolve_mean_file,
    )
    from sparknet_tpu.ops.layout import canonical_shape
    from sparknet_tpu.proto.text_format import Message

    batch = data_shape[0]
    train_path, test_path, stride, offset = spec_paths(text, "db:", pid, nproc)
    mean_cache: dict = {}

    def phase_params(n) -> dict:
        """``TransformConfig``'s fields from the first Data layer's
        transform_param of net ``n`` (ref: data_transformer.cpp: mean ->
        crop [random in TRAIN, center in TEST] -> mirror -> scale — the
        reference's DataLayer transforms every record).  ``data_scale``
        overrides the scale field (lenet_train_test.prototxt's 0.00390625
        without a prototxt edit)."""
        tp = next((l.lp.get_msg("transform_param") for l in n.input_layers
                   if getattr(l, "TYPE", "") == "Data"), Message())
        mean_img, mf = None, tp.get_str("mean_file")
        if mf:
            # Caffe CHECK-fails on an unreadable mean_file; silently
            # training without mean subtraction would be a wrong-result
            # bug.  CWD-relative first (Caffe), then walk-up from the
            # solver file, like net: paths.  Cached per resolved path:
            # the standard train_val layout declares the SAME
            # (ImageNet-scale) mean file in both phases — load it once.
            try:
                resolved = resolve_mean_file(mf, solver_path)
                if resolved not in mean_cache:
                    mean_cache[resolved] = load_mean_file(resolved)
                mean_img = mean_cache[resolved]
            except ValueError as e:
                raise SystemExit(str(e)) from None
        return {
            "crop_size": tp.get_int("crop_size", 0),
            "mirror": tp.get_bool("mirror", False),
            "mean_value": tuple(float(v) for v in tp.get_all("mean_value")),
            "mean_image": mean_img,
            "scale": data_scale or tp.get_float("scale", 1.0),
        }

    trainp = phase_params(net)
    # Caffe semantics: each phase's Data layer carries its OWN
    # transform_param — a TEST layer without one gets DEFAULTS (no
    # crop/mean), it does NOT inherit the train declaration.  The train
    # params cover the test stream only when the caller has no distinct
    # test net or it declares no Data layer at all.
    test_has_data = test_net is not None and any(
        getattr(l, "TYPE", "") == "Data" for l in test_net.input_layers)
    testp = phase_params(test_net) if test_has_data else trainp
    device_aug = device_augment(augment, trainer, prefetch)

    def check_geometry(path, got, want_shape, may_crop):
        """DB records are canonical (C, H, W): compare them with the
        canonical view of the net's (internal) blob.  Where a crop comes
        later (a worker's TransformStage, the device augment) records
        must be at least net-sized with matching channels; otherwise the
        net sees this exact shape."""
        got, want = tuple(got), tuple(canonical_shape(want_shape)[1:])
        if may_crop:
            ok = got[0] == want[0] and got[1] >= want[1] and got[2] >= want[2]
        else:
            ok = got == want
        if not ok:
            raise SystemExit(f"{path}: db images {got} do not match the "
                             f"net's data blob {want}")

    def db_stream(path, stride=1, offset=0, train=True) -> Feed:
        """Lazy cursor: nothing opens until the first call, so
        eval-only subcommands never touch the train DB; errors
        surface as clean SystemExits at first use."""
        state: dict = {}
        p = trainp if train else testp  # phase-specific declaration
        # with a device augment the TRAIN stream ships raw uint8 and the
        # transform runs in XLA (device_fn below); eval batches stay
        # host-transformed (off the hot loop, deterministic)
        raw = device_aug and train
        xform = None
        if not raw and (p["crop_size"] or p["mirror"]
                        or p["mean_image"] is not None or p["mean_value"]):
            try:
                xform = DataTransformer(TransformConfig(**p, seed=host_seed))
            except ValueError as e:  # e.g. mean_image AND mean_value
                raise SystemExit(f"transform_param: {e}") from None

        def fn(_, out=None):
            if "iter" not in state:
                try:
                    state["iter"] = db_minibatches(
                        path, batch, loop=True,
                        dtype=np.uint8 if raw else np.float32)
                    b = next(state["iter"])
                    for _ in range(offset):
                        b = next(state["iter"])
                except (OSError, ValueError) as e:
                    raise SystemExit(f"--data db: {path}: {e}") from None
            else:
                for _ in range(stride - 1):
                    next(state["iter"])
                # the cursor fills ``out`` (see ``takes_out``); its
                # first batch, above, is always a fresh array
                b = state["iter"].send(out)
            if xform is not None:
                try:
                    b = dict(b, data=xform(b["data"], train))
                except ValueError as e:  # e.g. crop > record size
                    raise SystemExit(f"--data db: {path}: {e}") from None
            elif not raw and p["scale"] != 1.0:
                b = dict(b, data=b["data"] * p["scale"])
            if "checked" not in state:
                state["checked"] = True
                want = data_shape
                if not train and test_net is not None:
                    # the test stream feeds the TEST net: check
                    # against ITS declared geometry (its own crop)
                    try:
                        want = feed_shapes(test_net, text, pid)["data"]
                    except (KeyError, SystemExit):
                        pass  # fall back to the train net's blob
                check_geometry(path, b["data"].shape[1:], want,
                               raw and p["crop_size"])
            return b

        # the batch is the cursor's own array unless a host transform
        # or scale makes a new one from it
        return Feed(fn, takes_out=xform is None and (raw or p["scale"] == 1.0))

    train = db_stream(train_path, stride, offset)
    if device_aug:
        attach_device_augment(train, TransformConfig(**trainp), pid, seed)

    def pipeline_factory(num_batches, start_index=0, workers=None):
        """Process-feed twin of the threaded db cursor: a RecordShardSource
        byte-offset index makes the DB epoch-addressable (data/records.py),
        decode runs IN the ring workers (the `decode` stage — the
        parallelizable host work), and the wire is built in the internal
        layout natively.  Host-transform arm composes a worker-side
        TransformStage; the device arm ships raw uint8 and augments
        post-placement in XLA."""
        from sparknet_tpu.data.pipeline import ProcessPipeline, TransformStage
        from sparknet_tpu.data.records import RecordShardSource
        from sparknet_tpu.ops.layout import is_nhwc

        lay = "nhwc" if is_nhwc() else "nchw"
        try:
            src = RecordShardSource(
                train_path, batch, layout=lay, stride=stride, offset=offset)
        except (OSError, ValueError) as e:
            raise SystemExit(f"--data db: {train_path}: {e}") from None
        # with a crop declared EITHER arm crops the records down
        check_geometry(train_path, peek_db_shape(train_path), data_shape,
                       trainp["crop_size"])
        stage = None
        if not device_aug:
            stage = TransformStage(TransformConfig(**trainp, seed=host_seed),
                                   train=True, layout=lay)
        return ProcessPipeline(
            src, stage, num_batches=num_batches, start_index=start_index,
            workers=workers, name="feed.db")

    if probe_record_backend(train_path) in ("record", "lmdb"):
        # LevelDB keeps the threaded cursor: snappy blocks have no
        # per-record byte offsets to index (RecordShardSource's
        # refusal names convert_db as the migration)
        train.pipeline_factory = pipeline_factory
    return (read_span(internalize(train), batch),
            internalize(db_stream(test_path, train=False)))


def _open_tokens(text, data_shape, pid, nproc, **_):
    """Language-model training from a tokenised corpus: one flat uint16
    token file (data/text.py token_windows), windows of seq_len + 1 ->
    data / label [batch, seq_len].  "tokens:train[,test]"; {proc} and the
    shared-file batch interleave as for db:."""
    from sparknet_tpu.data.text import token_windows

    if len(data_shape) != 2:
        raise SystemExit(
            f"--data tokens: feeds a [batch, seq_len] data blob; the "
            f"net's is {tuple(data_shape)}")
    batch, seq_len = data_shape
    train_path, test_path, stride, offset = spec_paths(
        text, "tokens:", pid, nproc)
    try:
        train = token_windows(train_path, batch, seq_len,
                              stride=stride, offset=offset)
        test_fn = token_windows(test_path, batch, seq_len)
    except (OSError, ValueError) as e:
        raise SystemExit(f"--data tokens: {e}") from None
    return read_span(Feed(train, takes_out=train.takes_out), batch,
                     tokens=batch * seq_len), test_fn


def _open_synthetic(data_shape, pid, **_):
    batch = data_shape[0]
    rs = np.random.RandomState(pid)

    def batch_from(rs):
        return {
            "data": (rs.randn(*data_shape) * 50).astype(np.float32),
            "label": rs.randint(0, 10, batch).astype(np.int32),
        }

    def pipeline_factory(num_batches, start_index=0, workers=None):
        """Process-feed twin: per-INDEX stateless seeding (workers
        cannot share the train stream's sequential RandomState;
        synthetic batches carry no identity worth preserving, and
        determinism per (pid, index) keeps the worker assignment pure).
        ``data_shape`` is already the INTERNAL layout — synthesis IS
        the wire, zero transposes in either orientation."""
        from sparknet_tpu.data.pipeline import DataFnSource, ProcessPipeline

        return ProcessPipeline(
            DataFnSource(lambda it: batch_from(np.random.RandomState(
                (pid * 1_000_003 + it) & 0x7FFFFFFF))),
            num_batches=num_batches, start_index=start_index,
            workers=workers, name="feed.synthetic")

    # the test stream: a stateless per-batch seed, the same on every process
    return (Feed(lambda it: batch_from(rs),
                 pipeline_factory=pipeline_factory),
            lambda b: batch_from(np.random.RandomState(100_000 + b)))


OPENERS = {
    "proto": _open_proto,
    "cifar:": _open_cifar,
    "db:": _open_db,
    "tokens:": _open_tokens,
    "synthetic": _open_synthetic,
}


def process_feed(train_fn, num_batches, start_index, log, workers=None,
                 prefetch=0, device_stage=True):
    """``Config.feed == "process"``: swap the thread feed for the
    shared-memory pipeline (``data/pipeline.py``).  Returns
    ``(context, data_fn)`` — the context owns the ring + (optionally)
    the double-buffered device-put stage and must wrap the train loop;
    the data_fn serves the solver's feed contract.

    ``device_stage=False`` keeps feeds HOST-side (the ParallelTrainer
    packs tau/global batches itself and owns its own device_put): the
    data fn is ``train_fn``'s feed around the ring, with its
    ``trainer_device_fn`` and its lock."""
    factory = getattr(train_fn, "pipeline_factory", None)
    if factory is None:
        raise SystemExit(
            "--feed process needs an index-addressable source a worker "
            "process can re-produce deterministically: synthetic, cifar:, "
            "and db: record/LMDB files (RecordShardSource byte-offset "
            "index, data/records.py) ride the ring; the remaining "
            "stateful cursors (proto listfiles, LevelDB) keep --feed "
            "threaded — convert LevelDB via data.createdb.convert_db to "
            "join")
    stack = contextlib.ExitStack()
    pipe = stack.enter_context(factory(
        num_batches=num_batches, start_index=start_index,
        workers=workers or None))
    if device_stage:
        from sparknet_tpu.data.pipeline import device_feed

        pf = stack.enter_context(device_feed(
            pipe, depth=max(prefetch, 2),
            device_fn=getattr(train_fn, "device_fn", None)))
        it = iter(pf)
        fn = lambda _it: next(it)  # noqa: E731 — the solver feed contract
    else:
        # a batch waits in a RoundBuffer past the ring's view-lifetime
        # window: stable copies (the host-buffer rule, data/rounds.py;
        # cheap: the wire is uint8 under --augment device)
        fn = train_fn.wrap(pipe.as_data_fn(copy=True), takes_out=False)
    log(f"feed: process pipeline ({pipe.workers} worker(s), "
        f"{pipe.slots} slots x {pipe.spec.slot_bytes:,} B"
        f"{', device stage' if device_stage else ''})")
    return stack, fn
