"""Utils (event log, signals, timing) + apps + CLI tests."""

import json
import os
import signal

import jax
import numpy as np
import pytest

from sparknet_tpu import models
from sparknet_tpu.common import Phase
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.data.cifar import write_synthetic_cifar
from sparknet_tpu.utils import EventLogger, SignalHandler, SolverAction
from sparknet_tpu.utils.timing import time_layers


# ---------------------------------------------------------------- utils
def test_event_logger_format(tmp_path):
    log = EventLogger(str(tmp_path), prefix="t", echo=False)
    log("hello")
    log("step", i=7)
    lines = open(log.path).read().splitlines()
    assert lines[0].startswith("start ")
    assert "hello" in lines[1]
    assert lines[2].endswith("step, i = 7")


def test_signal_handler_snapshot_then_stop():
    with SignalHandler() as sig:
        assert sig.check() is SolverAction.NONE
        os.kill(os.getpid(), signal.SIGHUP)
        assert sig.check() is SolverAction.SNAPSHOT
        assert sig.check() is SolverAction.NONE  # one-shot
        os.kill(os.getpid(), signal.SIGINT)
        assert sig.check() is SolverAction.STOP
    # uninstalled: default handlers restored
    assert signal.getsignal(signal.SIGHUP) not in (None,)


def test_time_layers_lenet():
    net = Network(models.lenet(2), Phase.TRAIN)
    variables = net.init(jax.random.PRNGKey(0))
    feeds = {
        "data": np.random.RandomState(0).randn(2, 1, 28, 28).astype(np.float32),
        "label": np.zeros(2, np.int32),
    }
    rows = time_layers(net, variables, feeds, iterations=1)
    names = [r["layer"] for r in rows]
    assert "conv1" in names and "loss" in names
    conv = next(r for r in rows if r["layer"] == "conv1")
    assert conv["forward_ms"] > 0
    assert conv["backward_ms"] is not None and conv["backward_ms"] > 0
    # accuracy is TEST-only (include { phase: TEST }, like the reference
    # prototxts) — absent from the TRAIN table, forward-only in the TEST one
    assert "accuracy" not in names
    test_rows = time_layers(
        Network(models.lenet(2), Phase.TEST), variables, feeds, iterations=1
    )
    acc = next(r for r in test_rows if r["layer"] == "accuracy")
    assert acc["forward_ms"] > 0  # non-differentiable: forward only


def test_cli_time_hlo_cost_analysis(capsys):
    """`tpunet time --hlo`: XLA cost model of the compiled step (the
    per-op HLO cost breakdown, SURVEY §5's `caffe time` analog)."""
    import json as _json

    from sparknet_tpu.cli import main

    assert main(["time", "--hlo", "--solver", "zoo:lenet", "--batch", "4"]) == 0
    out = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["flops_per_step"] > 1e6  # lenet fwd+bwd at batch 4
    assert out["hbm_bytes_per_step"] > 0
    assert out["batch"] == 4


def test_cli_time_trace_stages_banked(tmp_path, capsys):
    """`tpunet time --trace --trace-out`: the artifact is flushed after
    every stage (compile stats, untraced wall timing, short trace, full
    trace) so a failure mid-trace still leaves the earlier stages.  On CPU the
    final stage lands with measured wall numbers and empty device rows."""
    import json as _json

    from sparknet_tpu.cli import main

    out = tmp_path / "trace.artifact.json"
    assert main(["time", "--trace", "--trace-out", str(out),
                 "--solver", "zoo:lenet", "--batch", "4",
                 "--iterations", "2"]) == 0
    line = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["wall_ms_per_step"] > 0 and line["batch"] == 4
    art = _json.loads(out.read_text())
    assert art["stage"] == "final"
    # every earlier stage's fields survive in the artifact (the banking
    # is cumulative, so partial stages are supersets of their ancestors)
    assert art["gflop_per_step"] > 0            # stage: compiled
    assert art["wall_ms_per_step_untraced"] > 0  # stage: wall_timed
    assert "rows_short" in art                   # stage: trace_short
    assert art["img_per_sec"] > 0                # stage: final


def test_pull_shards_and_create_labelfile(tmp_path, capsys):
    """Dataset staging tools (ref: ec2/pull.py + ec2/create_labelfile.py)."""
    import io
    import tarfile

    from sparknet_tpu.cli import main

    store = tmp_path / "store"
    store.mkdir()
    for i in range(3):
        with tarfile.open(store / f"files-shuf-{i:03d}.tar", "w") as tar:
            for j in range(2):
                data = f"img {i}-{j}".encode()
                info = tarfile.TarInfo(name=f"n{i:04d}_{j}.JPEG")
                info.size = len(data)
                tar.addfile(info, io.BytesIO(data))
    out = tmp_path / "staged"
    assert main(["pull_shards", "--store", str(store),
                 "--start", "0", "--stop", "2", "--out", str(out)]) == 0
    staged = out / "000-002"
    files = sorted(p.name for p in staged.iterdir())
    assert len(files) == 4  # shards 0 and 1 only
    assert "n0002_0.JPEG" not in files

    # selection is by shard NUMBER in the filename, not list position:
    # with shard 001 deleted, [2, 3) still means shard 002
    (store / "files-shuf-001.tar").unlink()
    out2 = tmp_path / "staged2"
    assert main(["pull_shards", "--store", str(store),
                 "--start", "2", "--stop", "3", "--out", str(out2)]) == 0
    files2 = sorted(p.name for p in (out2 / "002-003").iterdir())
    assert files2 == ["n0002_0.JPEG", "n0002_1.JPEG"]

    # empty numeric range is an error, not a silent 0-file success
    import pytest as _pytest

    with _pytest.raises(SystemExit, match="no shards numbered"):
        main(["pull_shards", "--store", str(store),
              "--start", "7", "--stop", "9", "--out", str(out2)])

    master = tmp_path / "master_train.txt"
    master.write_text(
        "N0000_0.jpeg 7\nn0000_1.JPEG 3\nn0001_0.JPEG 1\n"
        "n0001_1.JPEG 2\nunrelated.JPEG 9\n"
    )
    labelfile = tmp_path / "train.txt"
    assert main(["create_labelfile", str(staged), str(master), str(labelfile)]) == 0
    lines = dict(l.split() for l in labelfile.read_text().splitlines())
    # case-normalized lookup; only staged files appear
    assert lines == {"n0000_0.JPEG": "7", "n0000_1.JPEG": "3",
                     "n0001_0.JPEG": "1", "n0001_1.JPEG": "2"}


# ---------------------------------------------------------------- apps
@pytest.fixture(scope="module")
def cifar_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("cifar")
    write_synthetic_cifar(str(d), seed=2)
    return str(d)


def test_cifar_app_runs(cifar_dir, tmp_path):
    from sparknet_tpu.apps import CifarApp

    app = CifarApp(cifar_dir, tau=2, batch=4, log_dir=str(tmp_path))
    scores = app.run(num_outer=2, num_test_batches=2)
    assert "accuracy" in scores and np.isfinite(scores["accuracy"])
    # event log recorded phases
    content = open(app.log.path).read()
    assert "training" in content and "testing" in content
    # snapshot path works
    p = app.snapshot(str(tmp_path / "snap"))
    assert os.path.exists(p)


def test_featurizer(cifar_dir):
    from sparknet_tpu.apps import FeaturizerApp
    from sparknet_tpu.net import TPUNet

    net = TPUNet(models.lenet_solver(), models.lenet(4))
    app = FeaturizerApp(net, feature_blob="ip1")
    feeds = [{
        "data": np.zeros((4, 1, 28, 28), np.float32),
        "label": np.zeros(4, np.int32),
    }]
    feats = list(app.featurize(feeds))
    assert feats[0].shape == (4, 500)
    with pytest.raises(KeyError):
        list(FeaturizerApp(net, "nope").featurize(feeds))


def test_imagenet_app_tau_feeds(tmp_path):
    """ImageNetApp packs tau x workers minibatches with the crop applied."""
    import io
    import tarfile
    from PIL import Image

    rs = np.random.RandomState(0)
    labels = {}
    tar_path = tmp_path / "shard0.tar"
    with tarfile.open(tar_path, "w") as tf:
        for i in range(8):
            name = f"img{i}.jpg"
            buf = io.BytesIO()
            Image.fromarray(rs.randint(0, 255, (64, 64, 3)).astype(np.uint8)).save(
                buf, format="JPEG")
            data = buf.getvalue()
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
            labels[name] = i % 3
    (tmp_path / "train.txt").write_text(
        "".join(f"{n} {l}\n" for n, l in labels.items()))

    from sparknet_tpu.apps.imagenet_app import ImageNetApp

    # tiny: alexnet at batch 2 never compiles here — only feed packing is
    # exercised, so stub the trainer-heavy ctor pieces via small model
    app = ImageNetApp.__new__(ImageNetApp)
    app.loader = __import__("sparknet_tpu.data", fromlist=["ImageNetLoader"]).ImageNetLoader(
        str(tmp_path), str(tmp_path / "train.txt"))
    app.batch = 2
    app.tau = 2
    app.num_workers = 1
    from sparknet_tpu.data import DataTransformer, TransformConfig
    app.transform = DataTransformer(TransformConfig(crop_size=48, mirror=True, seed=0))
    import sparknet_tpu.apps.imagenet_app as mod
    mod.RESIZE, old_resize = 64, mod.RESIZE
    mod.CROP, old_crop = 48, mod.CROP
    try:
        streams = [app.minibatch_stream(0)]
        feeds = app._tau_feeds(streams)
        assert feeds["data"].shape == (2, 2, 3, 48, 48)
        assert feeds["label"].shape == (2, 2)
    finally:
        mod.RESIZE, mod.CROP = old_resize, old_crop


def test_cifar_app_capacity_check(cifar_dir, tmp_path):
    """tau x global batch beyond the train set raises the clear error, not a
    numpy reshape failure."""
    from sparknet_tpu.apps import CifarApp

    app = CifarApp(cifar_dir, tau=2, batch=4, log_dir=str(tmp_path))
    app.tau = 1000  # force need > n
    with pytest.raises(ValueError, match="reduce tau"):
        app._train_feeds(0)


# ---------------------------------------------------------------- CLI
def test_cli_device_query(capsys):
    from sparknet_tpu.cli import main

    assert main(["device_query"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == len(jax.devices())
    assert json.loads(out[0])["platform"] == "cpu"


def test_cli_train_and_test_zoo_synthetic(tmp_path, monkeypatch, capsys):
    from sparknet_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    rc = main([
        "train", "--solver", "zoo:lenet", "--batch", "8",
        "--data", "synthetic", "--iterations", "3",
        "--test-iters", "2", "--output", "final",
    ])
    assert rc == 0
    assert os.path.exists("final.solverstate.npz")
    rc = main([
        "test", "--solver", "zoo:lenet", "--batch", "8",
        "--data", "synthetic", "--iterations", "2",
        "--snapshot", "final.solverstate.npz",
    ])
    assert rc == 0
    scores = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "accuracy" in scores


def test_net_root_walks_up_from_solver_file(tmp_path, monkeypatch):
    """A solver whose relative ``net:`` path is rooted at the tree top
    (the Caffe layout: run from the caffe root) must still resolve when
    tpunet runs from an unrelated CWD — cli._net_root walks up from the
    solver file (ref: examples/cifar10/train_full.sh runs build/tools/
    caffe from the repo root with examples/... paths)."""
    import argparse

    from sparknet_tpu.cli import _build_net_and_solver

    root = tmp_path / "tree"
    (root / "examples" / "toy").mkdir(parents=True)
    (root / "examples" / "toy" / "net.prototxt").write_text(
        'name: "toy"\n'
        'layer { name: "data" type: "Input" top: "data"\n'
        "  input_param { shape { dim: 2 dim: 3 } } }\n"
        'layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"\n'
        "  inner_product_param { num_output: 4 } }\n"
    )
    solver = root / "examples" / "toy" / "solver.prototxt"
    solver.write_text(
        'net: "examples/toy/net.prototxt"\nbase_lr: 0.1\nmax_iter: 1\n'
    )
    monkeypatch.chdir(tmp_path)  # NOT the tree root: CWD-relative fails
    args = argparse.Namespace(solver=str(solver), batch=None)
    net_param, cfg = _build_net_and_solver(args)
    assert net_param.get_str("name") == "toy"
    assert cfg.base_lr == 0.1


def test_cli_train_cifar_tau(cifar_dir, tmp_path, monkeypatch):
    from sparknet_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    rc = main([
        "train", "--solver", "zoo:cifar10_quick", "--batch", "4",
        "--data", f"cifar:{cifar_dir}", "--iterations", "4", "--tau", "2",
    ])
    assert rc == 0


def test_cli_train_cifar_device_augment(cifar_dir, tmp_path, monkeypatch):
    """--augment device: uint8 over the feed link, mean-subtract in XLA
    on the prefetch thread (DeviceAugment via device_fn)."""
    from sparknet_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    rc = main([
        "train", "--solver", "zoo:cifar10_quick", "--batch", "4",
        "--data", f"cifar:{cifar_dir}", "--iterations", "4",
        "--prefetch", "2", "--augment", "device",
    ])
    assert rc == 0


def test_cli_train_db_device_augment(tmp_path, monkeypatch):
    """--augment device on a db: source — records larger than the net's
    blob ship as raw uint8 and crop/mirror/scale run in XLA on the
    prefetch thread (the ImageNet 256-px-DB → 227-crop recipe shape)."""
    import numpy as np

    from sparknet_tpu.cli import main
    from sparknet_tpu.data.createdb import create_db

    monkeypatch.chdir(tmp_path)
    rs = np.random.RandomState(0)
    samples = [(rs.randint(0, 255, (3, 16, 16)).astype(np.uint8), i % 4)
               for i in range(24)]
    db = str(tmp_path / "aug_lmdb")
    create_db(db, samples, backend="lmdb")

    (tmp_path / "net.prototxt").write_text(
        'name: "devaug"\n'
        'layer { name: "d" type: "Data" top: "data" top: "label"\n'
        '  data_param { source: "gone_lmdb" batch_size: 6 }\n'
        "  transform_param { crop_size: 12 mirror: true scale: 0.0039 }\n"
        "}\n"
        'layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"\n'
        "  inner_product_param { num_output: 4 } }\n"
        'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
        'bottom: "label" top: "loss" }\n'
    )
    (tmp_path / "solver.prototxt").write_text(
        'net: "net.prototxt"\nbase_lr: 0.01\nmax_iter: 4\ndisplay: 0\n'
    )
    rc = main([
        "train", "--solver", str(tmp_path / "solver.prototxt"),
        "--data", f"db:{db}", "--iterations", "4",
        "--prefetch", "2", "--augment", "device",
        "--output", str(tmp_path / "out"),
    ])
    assert rc == 0
    assert (tmp_path / "out.solverstate.npz").exists()


def test_cli_device_augment_guards(cifar_dir, tmp_path, monkeypatch):
    from sparknet_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    base = ["train", "--solver", "zoo:cifar10_quick", "--batch", "4",
            "--iterations", "2"]
    with pytest.raises(SystemExit, match="--prefetch"):
        main(base + ["--data", f"cifar:{cifar_dir}", "--augment", "device"])
    with pytest.raises(SystemExit, match="cifar"):
        main(base + ["--data", "synthetic", "--prefetch", "2",
                     "--augment", "device"])
    # the trainer path needs NO async-feed precondition: the augment
    # runs post-placement via ParallelTrainer.feed_device_fn, so
    # --augment device --tau trains end-to-end (uint8 tau wire)
    rc = main(base + ["--data", f"cifar:{cifar_dir}", "--augment",
                      "device", "--tau", "2", "--output",
                      str(tmp_path / "aug_tau")])
    assert rc == 0
    assert (tmp_path / "aug_tau.solverstate.npz").exists()


def test_cli_time_lenet(capsys):
    from sparknet_tpu.cli import main

    rc = main(["time", "--solver", "zoo:lenet", "--batch", "2",
               "--data", "synthetic", "--iterations", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "conv1" in out and "TOTAL" in out


def test_profiling_trace_writes_files(tmp_path):
    from sparknet_tpu.utils import profiling

    d = str(tmp_path / "prof")
    with profiling.trace(d):
        jnp_sum = jax.jit(lambda x: x * 2)(np.ones(16, np.float32))
        jax.block_until_ready(jnp_sum)
    # a plugins/profile/<ts>/ tree with at least one trace artifact
    found = [f for root, _, fs in os.walk(d) for f in fs]
    assert found, "profiler produced no artifacts"


def test_hbm_live_is_the_fullest_device_or_nothing():
    """``hbm_live`` (which took ``device_memory_stats``' place): the
    largest ``bytes_in_use``, and no stat at all, not 0, from a backend
    that exposes nothing (the CPU)."""
    import jax

    from sparknet_tpu.utils.profiling import hbm_live

    class Chip:
        def __init__(self, stats):
            self.memory_stats = lambda: stats

    assert hbm_live(jax.devices()) == {}
    assert hbm_live([Chip({"bytes_in_use": 5}), Chip(None),
                     Chip({"bytes_in_use": 9, "bytes_limit": 16})]) == {
        "hbm_live_bytes": 9}


def test_cli_dataset_tools_pipeline(tmp_path, monkeypatch, capsys):
    """convert_imageset -> compute_image_mean -> extract_features chain."""
    import io as _io
    from PIL import Image

    from sparknet_tpu.cli import main

    native = pytest.importorskip("sparknet_tpu.native")
    if not native.available():
        pytest.skip("native record DB unavailable")

    rs = np.random.RandomState(0)
    imgdir = tmp_path / "imgs"
    imgdir.mkdir()
    lines = []
    for i in range(6):
        arr = rs.randint(0, 255, (20, 20, 3)).astype(np.uint8)
        Image.fromarray(arr).save(imgdir / f"im{i}.jpg")
        lines.append(f"im{i}.jpg {i % 3}")
    listfile = tmp_path / "list.txt"
    listfile.write_text("\n".join(lines) + "\n")

    monkeypatch.chdir(tmp_path)
    db = str(tmp_path / "set.sndb")
    assert main(["convert_imageset", "--root", str(imgdir), "--listfile",
                 str(listfile), "--db", db, "--resize", "16"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["records"] == 6

    assert main(["compute_image_mean", "--db", db, "--out",
                 str(tmp_path / "mean.npy"), "--batch", "2"]) == 0
    mean = np.load(tmp_path / "mean.npy")
    assert mean.shape == (3, 16, 16)

    assert main(["extract_features", "--solver", "zoo:lenet", "--batch", "4",
                 "--data", "synthetic", "--iterations", "2",
                 "--blob", "ip1", "--out", str(tmp_path / "feats.npy")]) == 0
    feats = np.load(tmp_path / "feats.npy")
    assert feats.shape == (8, 500)


def test_db_apps_cifar_and_imagenet(tmp_path, cifar_dir):
    """CifarDBApp materializes DBs and trains; ImageNetCreateDBApp +
    ImageNetRunDBApp round-trip through the record-DB pipeline."""
    import io as _io
    import tarfile
    from PIL import Image

    native = pytest.importorskip("sparknet_tpu.native")
    if not native.available():
        pytest.skip("native record DB unavailable")

    from sparknet_tpu.apps.db_apps import CifarDBApp, ImageNetCreateDBApp

    app = CifarDBApp(cifar_dir, str(tmp_path / "dbs"), batch=10,
                     log_dir=str(tmp_path))
    scores = app.run(num_iters=3, test_batches=2)
    assert "accuracy" in scores
    # DBs persisted; a second construction reuses them
    app2 = CifarDBApp(cifar_dir, str(tmp_path / "dbs"), batch=10,
                      log_dir=str(tmp_path))
    assert app2.mean_image.shape == (3, 32, 32)

    # the reference's actual backend (CifarDBApp.scala writes LevelDB)
    app3 = CifarDBApp(cifar_dir, str(tmp_path / "dbs_ldb"), batch=10,
                      log_dir=str(tmp_path), backend="leveldb")
    scores3 = app3.run(num_iters=2, test_batches=1)
    assert "accuracy" in scores3

    # a crash mid-materialize leaves a half-DB (no done marker):
    # reconstruction must clear and rebuild instead of hanging on reuse
    import shutil

    shutil.rmtree(str(tmp_path / "dbs_ldb" / "cifar_test_leveldb"))
    (tmp_path / "dbs_ldb" / "cifar_test_leveldb").mkdir()  # empty husk
    os.remove(str(tmp_path / "dbs_ldb" / ".materialized_leveldb"))
    app4 = CifarDBApp(cifar_dir, str(tmp_path / "dbs_ldb"), batch=10,
                      log_dir=str(tmp_path), backend="leveldb")
    assert app4.run(num_iters=1, test_batches=1)["accuracy"] >= 0.0

    with pytest.raises(ValueError, match="unknown db backend"):
        CifarDBApp(cifar_dir, str(tmp_path / "x"),
                   log_dir=str(tmp_path), backend="lvldb")

    # tiny imagenet-style shard
    rs = np.random.RandomState(0)
    labels = {}
    with tarfile.open(tmp_path / "s0.tar", "w") as tf:
        for i in range(5):
            name = f"i{i}.jpg"
            buf = _io.BytesIO()
            Image.fromarray(rs.randint(0, 255, (40, 40, 3)).astype(np.uint8)).save(
                buf, format="JPEG")
            data = buf.getvalue()
            ti = tarfile.TarInfo(name)
            ti.size = len(data)
            tf.addfile(ti, _io.BytesIO(data))
            labels[name] = i
    (tmp_path / "labels.txt").write_text(
        "".join(f"{n} {l}\n" for n, l in labels.items()))
    creator = ImageNetCreateDBApp(str(tmp_path), str(tmp_path / "labels.txt"),
                                  str(tmp_path / "in_dbs"), resize=32, batch=2)
    info = creator.run()
    assert info["workers"][0]["records"] == 4  # 2 full batches of 2
    mean = np.load(info["mean"])
    assert mean.shape == (3, 32, 32)

    # the reference's actual backend, per-worker LevelDBs
    from sparknet_tpu.data.createdb import db_minibatches
    from sparknet_tpu.data.leveldb_io import is_leveldb

    creator2 = ImageNetCreateDBApp(
        str(tmp_path), str(tmp_path / "labels.txt"),
        str(tmp_path / "in_dbs_ldb"), resize=32, batch=2, backend="leveldb")
    info2 = creator2.run()
    db2 = info2["workers"][0]["db"]
    assert is_leveldb(db2) and info2["workers"][0]["records"] == 4
    assert next(db_minibatches(db2, 4))["data"].shape == (4, 3, 32, 32)


def test_cli_time_fused(capsys):
    from sparknet_tpu.cli import main

    rc = main(["time", "--solver", "zoo:lenet", "--batch", "4",
               "--data", "synthetic", "--iterations", "2", "--fused"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["batch"] == 4 and out["fused_step_ms"] > 0


def test_cli_train_profile(tmp_path, monkeypatch):
    from sparknet_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    rc = main(["train", "--solver", "zoo:lenet", "--batch", "4",
               "--data", "synthetic", "--iterations", "2",
               "--profile", str(tmp_path / "prof")])
    assert rc == 0
    found = [f for root, _, fs in os.walk(tmp_path / "prof") for f in fs]
    assert found, "no profiler artifacts written"


def test_cli_train_finetune_weights(tmp_path, capsys, monkeypatch):
    """`tpunet train --weights model.caffemodel` copies params by layer
    name before training (ref: caffe.cpp:184-189 CopyLayers /
    finetune_flickr_style)."""
    import json as _json

    from sparknet_tpu import models
    from sparknet_tpu.cli import main
    from sparknet_tpu.net import TPUNet, copy_caffemodel_params
    from sparknet_tpu.solvers.solver import SolverConfig

    monkeypatch.chdir(tmp_path)  # cmd_train writes its event log to cwd
    donor = TPUNet(SolverConfig(), models.lenet(4))
    weights = str(tmp_path / "donor.caffemodel")
    donor.save_caffemodel(weights)
    w_donor = np.asarray(donor.solver.variables.params["conv1"][0])

    out_prefix = str(tmp_path / "ft")
    assert main([
        "train", "--solver", "zoo:lenet", "--batch", "4",
        "--iterations", "1", "--weights", weights, "--output", out_prefix,
    ]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    meta = _json.loads(lines[0])
    assert meta["finetune_from"] == weights
    assert "conv1" in meta["layers_loaded"]
    # the copy itself delivers the donor's values (not just metadata):
    # a fresh net finetuned from the file starts at w_donor exactly
    fresh = TPUNet(SolverConfig(), models.lenet(4))
    params, loaded = copy_caffemodel_params(
        fresh.solver.variables.params, weights
    )
    assert "conv1" in loaded
    assert np.array_equal(np.asarray(params["conv1"][0]), w_donor)


def test_parse_log_tables(tmp_path):
    """ref: tools/extra/parse_log.py — train/test tables from a mixed log."""
    from sparknet_tpu.utils.log_parse import parse_log, parse_log_to_csv

    log = tmp_path / "tpunet_train_123.txt"
    log.write_text(
        "start 123\n"
        "0.100: profiling -> /tmp/x\n"
        "Iteration 100, loss = 2.2984, lr = 0.001\n"
        "1.500: loss: 2.10000, i = 150\n"
        "Iteration 200, loss = 0.68188, lr = 0.0005\n"
        "2.750: scores: {'accuracy': 0.727, 'loss': 0.6228}, i = 200\n"
        "3.000: scores: {'accuracy': 0.939, 'loss': 0.2027}\n"
        "garbage line that matches nothing\n"
        "192.168.0.1: connection refused\n"
    )
    train_rows, test_rows = parse_log(str(log))
    assert [r["NumIters"] for r in train_rows] == [100, 150, 200]
    assert train_rows[0]["LearningRate"] == 0.001
    assert train_rows[1] == {"NumIters": 150, "loss": 2.1, "Seconds": 1.5}
    assert train_rows[2]["loss"] == 0.68188
    assert [r["NumIters"] for r in test_rows] == [200, 200]
    assert test_rows[0]["accuracy"] == 0.727
    assert test_rows[1]["Seconds"] == 3.0

    train_csv, test_csv = parse_log_to_csv(str(log))
    header = open(train_csv).readline().strip().split(",")
    assert header[0] == "NumIters" and "loss" in header
    rows = open(test_csv).read().strip().splitlines()
    assert len(rows) == 3  # header + 2
    assert rows[0].startswith("NumIters,Seconds,accuracy")

    # stdout captures carry both the display line and its event-log mirror:
    # one merged row per iteration, display fields winning
    log2 = tmp_path / "stdout_capture.log"
    log2.write_text(
        "Iteration 100, loss = 2.0, lr = 0.001\n"
        "5.000: loss: 2.10000, i = 100\n"
    )
    merged, _ = parse_log(str(log2))
    assert merged == [
        {"NumIters": 100, "loss": 2.0, "LearningRate": 0.001, "Seconds": 5.0}
    ]

    # out_dir that does not exist yet is created
    t2, _ = parse_log_to_csv(str(log2), str(tmp_path / "results"))
    assert open(t2).readline().startswith("NumIters")


def test_cli_parse_log_roundtrip(tmp_path, monkeypatch, capsys):
    """End to end: tpunet train writes a log parse_log can tabulate."""
    import glob

    from sparknet_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    # Default log dir is the system tempdir; pin it to the sandbox to
    # exercise the SPARKNET_TRAIN_LOG_DIR route and keep the glob local.
    monkeypatch.setenv("SPARKNET_TRAIN_LOG_DIR", str(tmp_path))
    assert main([
        "train", "--solver", "zoo:lenet", "--batch", "8",
        "--data", "synthetic", "--iterations", "3",
        "--test-iters", "2", "--output", "final",
    ]) == 0
    (logfile,) = glob.glob("tpunet_train_*.txt")
    capsys.readouterr()
    assert main(["parse_log", logfile, str(tmp_path)]) == 0
    paths = json.loads(capsys.readouterr().out.strip())
    test_rows = open(paths["test"]).read().strip().splitlines()
    assert len(test_rows) == 2  # header + the --test-iters scores line
    assert "accuracy" in test_rows[0]
    assert test_rows[1].startswith("3,")  # scores stamped with i=<final iter>


def test_cli_deprecated_tools():
    from sparknet_tpu.cli import main

    for cmd in ("train_net", "finetune_net", "test_net", "net_speed_benchmark"):
        with pytest.raises(SystemExit, match="Deprecated"):
            main([cmd, "whatever.prototxt"])


def test_cli_train_multihost_two_processes(tmp_path):
    """tpunet train --distributed across 2 processes: DCN bring-up via
    CLI flags, per-process synthetic shards, both exit clean."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"

    def spawn(pid):
        return subprocess.Popen(
            [sys.executable, "-m", "sparknet_tpu.cli", "--platform", "cpu",
             "train", "--solver", "zoo:lenet", "--batch", "8",
             "--data", "synthetic", "--iterations", "2", "--distributed",
             "--coordinator", f"127.0.0.1:{port}", "--num-processes", "2",
             "--process-id", str(pid), "--output", str(tmp_path / f"out{pid}")],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=str(tmp_path),
        )

    procs = [spawn(0), spawn(1)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            p.poll() is None and p.kill()
    if any(p.returncode != 0 for p in procs):
        # known env drift (CHANGES.md PR 3/7: "fails identically at the
        # pre-PR tree"): the CPU backend's multiprocess device_put
        # rejection means the capability under test does not exist here
        # — skip like test_multihost_two_process_cluster does instead
        # of paying the re-verification tax every PR
        from conftest import skip_if_cpu_multiprocess_drift

        skip_if_cpu_multiprocess_drift(outs)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
    assert any("distributed: process" in o for o in outs)


def test_plot_training_log(tmp_path, capsys):
    """ref: tools/extra/plot_training_log.py.example — chart types render
    from a parsed log; missing-table requests fail clearly."""
    from sparknet_tpu.cli import main

    log = tmp_path / "run.txt"
    log.write_text(
        "Iteration 100, loss = 2.0, lr = 0.01\n"
        "10.000: loss: 1.50000, i = 200\n"
        "Iteration 300, loss = 1.0, lr = 0.005\n"
        "20.000: scores: {'accuracy': 0.5, 'loss': 1.2}, i = 300\n"
        "30.000: scores: {'accuracy': 0.8, 'loss': 0.6}, i = 600\n"
    )
    for ct in (0, 6, 4):
        out = tmp_path / f"chart{ct}.png"
        assert main(["plot_training_log", str(ct), str(out), str(log)]) == 0
        assert out.exists() and out.stat().st_size > 1000

    from sparknet_tpu.utils.plotting import plot_chart

    with pytest.raises(ValueError, match="unknown chart type"):
        plot_chart(9, str(log), str(tmp_path / "x.png"))
    empty = tmp_path / "empty.txt"
    empty.write_text("nothing here\n")
    with pytest.raises(ValueError, match="no .*rows"):
        plot_chart(0, str(empty), str(tmp_path / "x.png"))


def test_resize_images_tree(tmp_path, capsys):
    """ref: tools/extra/resize_and_crop_images.py — shorter-side resize +
    center crop over a tree, structure preserved, broken files survive."""
    from PIL import Image

    from sparknet_tpu.cli import main

    src = tmp_path / "in"
    (src / "synset_a").mkdir(parents=True)
    (src / "synset_b").mkdir()
    Image.new("RGB", (100, 60), (200, 10, 10)).save(src / "synset_a" / "wide.jpg")
    Image.new("RGB", (30, 90), (10, 200, 10)).save(src / "synset_b" / "tall.png")
    (src / "synset_b" / "broken.jpg").write_bytes(b"not an image")

    out = tmp_path / "out"
    # workers=2 exercises the multiprocessing.Pool path (worker fn and
    # args must stay picklable/spawn-safe — the default CLI path)
    rc = main([
        "resize_images", "--input-folder", str(src),
        "--output-folder", str(out), "--side", "32", "--workers", "2",
    ])
    assert rc == 1  # broken.jpg reported
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec == {"resized": 2, "errors": 1}
    for rel in ("synset_a/wide.jpg", "synset_b/tall.png"):
        with Image.open(out / rel) as img:
            assert img.size == (32, 32)


def test_cli_train_elastic(tmp_path, monkeypatch):
    """tpunet train --elastic-alpha: EASGD through the CLI (tau=1 and
    tau>1 both take the stacked feed contract)."""
    from sparknet_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    n = len(jax.devices())
    for tau in (1, 2):
        rc = main([
            "train", "--solver", "zoo:lenet", "--batch", "4",
            "--data", "synthetic", "--iterations", "2", "--tau", str(tau),
            "--elastic-alpha", str(0.9 / n), "--output", f"e{tau}",
        ])
        assert rc == 0
        assert os.path.exists(f"e{tau}.solverstate.npz")


def test_cli_test_weights(tmp_path, monkeypatch, capsys):
    """tpunet test --weights: score a caffemodel directly (the reference's
    canonical `caffe test --weights` usage)."""
    from sparknet_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    assert main([
        "train", "--solver", "zoo:lenet", "--batch", "8",
        "--data", "synthetic", "--iterations", "2", "--output", "m",
    ]) == 0
    capsys.readouterr()
    assert main([
        "test", "--solver", "zoo:lenet", "--batch", "8",
        "--data", "synthetic", "--iterations", "2",
        "--weights", "m.caffemodel",
    ]) == 0
    scores = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "accuracy" in scores

    with pytest.raises(SystemExit, match="mutually exclusive"):
        main(["test", "--solver", "zoo:lenet", "--batch", "8",
              "--data", "synthetic", "--snapshot", "m.solverstate.npz",
              "--weights", "m.caffemodel"])

    # extract_features from the same caffemodel (the reference tool's
    # pretrained_net_param argument, extract_features.cpp)
    capsys.readouterr()
    assert main([
        "extract_features", "--solver", "zoo:lenet", "--batch", "8",
        "--data", "synthetic", "--iterations", "2",
        "--weights", "m.caffemodel", "--blob", "ip1", "--out", "feats.npy",
    ]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["shape"] == [16, 500]  # 2 batches x 8, ip1 width


def test_cli_bench_brew(capsys, monkeypatch):
    """tpunet bench on a pinned CPU: the code path runs as a REHEARSAL —
    one JSON line that names its device and never carries the device
    metric's name or a measured stamp."""
    from sparknet_tpu.cli import main

    # conftest pins JAX_PLATFORMS=cpu, which bench.py reads as the
    # explicit rehearsal request; assert that coupling so a conftest
    # change fails here
    assert os.environ.get("JAX_PLATFORMS") == "cpu"
    assert main(["bench", "--batch", "4", "--dtype", "f32"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "alexnet_train_cpu_rehearsal"
    assert rec["measured"] is False and rec["rehearsal"] is True
    assert rec["platform"] == "cpu" and rec["device_kind"]
    assert rec["device_count"] >= 1
    assert "mfu" not in rec and "vs_baseline" not in rec
    assert rec["value"] > 0


def test_bench_without_a_chip_exits_nonzero_and_prints_no_value():
    """No chip and no explicit CPU pin: jax falls back to the CPU by
    itself, and bench.py must fail rather than report — nonzero exit,
    nothing on stdout."""
    import subprocess
    import sys as _sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # unpinned: whatever jax finds
    env.update({"SPARKNET_BENCH_BATCH": "4", "PYTHONPATH": root})
    out = subprocess.run(
        [_sys.executable, os.path.join(root, "bench.py")],
        capture_output=True, text=True, env=env, timeout=300, cwd=root)
    assert out.returncode != 0, (out.stdout + out.stderr)[-1500:]
    assert out.stdout.strip() == "", out.stdout[-500:]
    assert "no accelerator" in out.stderr


def test_cli_train_distributed_scan(tmp_path, monkeypatch):
    """tpunet train --distributed --scan N: tau=1 sync-SGD rounds fused
    N per dispatch (ParallelTrainer.train_rounds) through the CLI."""
    from sparknet_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    assert main([
        "train", "--solver", "zoo:lenet", "--batch", "4",
        "--data", "synthetic", "--iterations", "4", "--distributed",
        "--scan", "2", "--output", str(tmp_path / "out"),
    ]) == 0
    assert (tmp_path / "out.solverstate.npz").exists()


def test_cli_train_dtype_bf16(tmp_path, monkeypatch):
    """--dtype bf16 on the train brew: the central dispatch point sets
    the global compute dtype before any net is built (mixed precision
    as a first-class CLI path, not just bench env plumbing)."""
    import jax.numpy as jnp

    from sparknet_tpu import cli
    from sparknet_tpu.common import get_config, set_config

    monkeypatch.chdir(tmp_path)
    try:
        rc = cli.main(["train", "--solver", "zoo:lenet", "--batch", "4",
                       "--dtype", "bf16", "--iterations", "1",
                       "--data", "synthetic"])
        assert rc == 0
        # the dispatch point RESTORES the global dtype afterwards (an
        # in-process cli.main() must not leak bf16 into the caller)
        assert get_config().compute_dtype == jnp.float32
        # and the dtype took EFFECT during the run: the staged trace
        # artifact banks the active compute dtype at build time
        import json as _json

        rc2 = cli.main(["time", "--solver", "zoo:lenet", "--batch", "4",
                        "--dtype", "bf16", "--iterations", "1", "--trace",
                        "--trace-out", str(tmp_path / "t.json")])
        assert rc2 == 0
        art = _json.load(open(tmp_path / "t.json"))
        assert art["dtype"] == "bf16"
        assert get_config().compute_dtype == jnp.float32
    finally:
        set_config(compute_dtype=jnp.float32)
