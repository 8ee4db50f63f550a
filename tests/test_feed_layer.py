"""The feed as a layer (``sparknet_tpu/data/feed.py``, ``rounds.py``):
what the ``Feed`` type guarantees across a wrapper, which way the imports
point, and that every ``--data`` kind the CLI names has an opener.
"""

import ast
import pathlib

import numpy as np
import pytest

from sparknet_tpu import cli
from sparknet_tpu.data import feed as feed_mod
from sparknet_tpu.data.createdb import create_db
from sparknet_tpu.data.feed import OPENERS, Feed, internalize, read_span

PACKAGE = pathlib.Path(cli.__file__).parent

NET = (
    'name: "layer"\n'
    'layer { name: "d" type: "Data" top: "data" top: "label"\n'
    '  data_param { source: "unused" batch_size: 4 }\n'
    "  transform_param { crop_size: 12 mirror: true } }\n"
    'layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"\n'
    "  inner_product_param { num_output: 4 } }\n"
    'layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" '
    'bottom: "label" top: "loss" }\n'
)


# ------------------------------------------------------------- (a) the type
@pytest.mark.parametrize("field", [*Feed.FIELDS, "lock"])
def test_a_wrapper_keeps_every_field_and_the_same_lock(field):
    inner = Feed(lambda it, out=None: {"it": it}, takes_out=True,
                 device_fn=object(), trainer_device_fn=object(),
                 pipeline_factory=object())
    outer = inner.wrap(lambda it, out=None: dict(inner(it, out=out), w=1))
    assert getattr(outer, field) is getattr(inner, field)
    assert outer(3) == {"it": 3, "w": 1}
    # the old names still read as attributes, a plain fn with the defaults
    assert getattr(outer, "device_fn", None) is inner.device_fn
    assert getattr(lambda it: None, "takes_out", False) is False
    # and only what a wrapper names changes
    assert inner.wrap(outer, takes_out=False).takes_out is False


def test_a_db_feed_keeps_both_device_fns_through_its_wrappers_under_nhwc(
        tmp_path):
    """``--augment device`` over ``db:``: the feed ``open_feeds`` returns
    went through ``internalize`` and ``read_span``; under ``nhwc`` both
    wrap it, and the augment must still be there for the prefetcher
    (``device_fn``) and the trainer (``trainer_device_fn``)."""
    rs = np.random.RandomState(0)
    db = str(tmp_path / "db")
    create_db(db, [(rs.randint(0, 255, (3, 16, 16)).astype(np.uint8), i % 4)
                   for i in range(8)])
    (tmp_path / "net.prototxt").write_text(NET)
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path}/net.prototxt"\nbase_lr: 0.01\nmax_iter: 10\n')
    seen = {}

    def as_train(args):
        net_param, solver_cfg = cli._build_net_and_solver(args)
        solver = cli._make_solver(solver_cfg, net_param, args)
        train, _ = cli._data_fns(args, solver.train_net,
                                 test_net=solver.test_net)
        seen.update(train=train, batch=train(0),
                    rewrapped=read_span(internalize(train), 4))
        return 0

    orig = cli.cmd_train
    cli.cmd_train = as_train
    try:
        assert cli.main(["train", "--solver", str(tmp_path / "solver.prototxt"),
                         "--data", f"db:{db}", "--augment", "device",
                         "--prefetch", "2", "--layout", "nhwc"]) == 0
    finally:
        cli.cmd_train = orig
    train = seen["train"]
    assert isinstance(train, Feed) and train.takes_out
    assert callable(train.device_fn) and callable(train.trainer_device_fn)
    assert callable(train.pipeline_factory)
    assert seen["batch"]["data"].shape == (4, 16, 16, 3)  # raw, internal
    for field in (*Feed.FIELDS, "lock"):
        assert getattr(seen["rewrapped"], field) is getattr(train, field)


# -------------------------------------------------------- (b) the direction
def imported(path):
    """Every module a file imports, at any depth (lazy imports too)."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert not node.level, f"{path}: the scan reads absolute imports"
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


def test_nothing_imports_the_cli_and_the_feed_imports_nothing_above_it():
    """CLI -> feed -> solver / trainer -> compiler -> ops, one way."""
    above_the_feed = tuple(f"sparknet_tpu.{m}" for m in
                           ("cli", "solvers", "parallel", "serve", "loop"))
    files = sorted(PACKAGE.rglob("*.py"))
    assert PACKAGE / "data" / "feed.py" in files
    bad = []
    for path in files:
        banned = (above_the_feed if PACKAGE / "data" in path.parents
                  else ("sparknet_tpu.cli",))
        bad += [f"{path.relative_to(PACKAGE)}: {name}"
                for name in imported(path) if path.name != "cli.py"
                and any(name == b or name.startswith(b + ".")
                        for b in banned)]
    assert not bad, bad


# ---------------------------------------------------------- (c) the openers
def test_every_data_kind_the_help_names_has_an_opener(capsys):
    with pytest.raises(SystemExit):
        cli.main(["train", "--help"])
    text = " ".join(capsys.readouterr().out.split())
    kinds = text.split("--data DATA ")[1].split(" --data-scale")[0]
    # "auto (...) | cifar:<dir> | db:<path>[,..] | ... | proto (...) | synthetic"
    named = {part.split()[0].split("<")[0] for part in kinds.split(" | ")}
    assert named - {"auto"} == set(OPENERS)

    class Net:  # what an opener is asked before it is found: the shapes
        input_layers = ()

        def feed_shapes(self):
            return {"data": (2, 3, 4, 4), "label": (2,)}

    for spec, parsed in (("proto", ("proto", "proto")),
                         ("db:x,y", ("db:", "db:x,y")),
                         ("auto", ("synthetic", "synthetic")),
                         ("lmdb:x", ("", "lmdb:x"))):
        assert feed_mod.parse_spec(spec, Net()) == parsed
    with pytest.raises(SystemExit, match="unknown --data source 'lmdb:x'"):
        feed_mod.open_feeds("lmdb:x", Net())
    train, test = feed_mod.open_feeds("synthetic", Net())
    assert isinstance(train, Feed) and train(0)["data"].shape == (2, 3, 4, 4)
    assert test(0)["label"].shape == (2,)
