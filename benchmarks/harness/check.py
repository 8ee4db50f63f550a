"""The comparison that decides ``correct``.

The program is held to the configuration's plain reference
(``benchmarks/reference/<configuration>.py``) outside the timed window:

(a) step-0 training loss and logits of a seeded sample;
(b) the first step's parameter change on two leaves, against the
    reference gradient put through Caffe's SGD rule, and -- where the
    reference allows it -- the exact weight-decay-only update that an
    all-zero image batch leaves on the first conv's weights;
(c) (trainer cells) one tau=2 averaging round on fixed feeds: replicas
    bit-identical afterwards, the two leaves equal to the mean over
    workers of the reference's two local SGD steps.

The program computes in bf16 with f32 parameters, the reference in f32 at
``highest`` matmul precision, so (a) and (b) carry bf16's rounding:

* loss: |rel| <= 1e-3.  At initialisation the loss is ~ln(1000) and
  barely moves with the logits; PR 21 saw 5.5e-6.
* logits: max|err| / max|ref| <= 2e-2.  bf16 keeps 8 mantissa bits
  (2^-8 = 3.9e-3 per rounding) through 8 (AlexNet) to 53 (ResNet-50)
  layers; PR 21 saw 4.9e-3 on AlexNet.  Computing the loss layer or the
  logits in a dropped term (a missing bias, a wrong LRN constant, pooling
  floor for ceil) moves them by far more.
* update of the LAST leaf (last fc bias) on the sample: rel-L2 <= 2e-2;
  its gradient is mean(softmax - onehot), one bf16 matmul away from the
  f32 loss layer.  Measured 1.1e-3 to 1.2e-3 (my chip run, PR 22).  A
  wrong lr_mult (2 for 1) gives 1.0 or 0.5, a missing momentum or lr
  factor more.
* update of the FIRST leaf (first conv weight) on the sample: rel-L2 <=
  5e-1.  This gradient has passed backward through every layer in bf16
  (pooling ties, ReLU flips, LRN or BatchNorm): measured 0.244 to 0.248
  on the chip at 32 and 512-batch seeds and 0.24 to 0.27 on the CPU at 2
  images (per-sample gradients at initialisation are independent, so
  noise and signal both grow as sqrt(n) and the ratio does not shrink
  with the batch).  With --dtype f32 the same comparison gives 2e-6
  (benchmarks/tests/test_reference.py holds that on the CPU).  The bound
  still catches a factor-2 error (>= 0.5) and a sign or missing term.
* zero-batch update of the first conv weight: rel-L2 <= 1e-5.  The
  gradient is exactly zero, so the update is lr*wd*w in f32: dropping
  the weight-decay term gives 1.0, and parameters kept in bf16 cannot
  represent a 5e-6 relative change at all (gives 1.0).
"""

from __future__ import annotations

import numpy as np

TOL = {"loss_rel": 1e-3, "logits_rel": 2e-2, "update_rel": 5e-1,
       "update_rel_last": 2e-2, "decay_exact_rel": 1e-5}
# a CPU rehearsal checks 1-2 images: bf16 noise does not average out and
# batch-of-two BatchNorm is ill-conditioned.  It walks the code; the
# chip run at 32 images is what holds the program.
TOL_REHEARSE = {"loss_rel": 1e-2, "logits_rel": 2e-1, "update_rel": 2.0,
                "update_rel_last": 2e-1, "decay_exact_rel": 1e-5}


def tolerances(ref, rehearse: bool = False) -> dict:
    """Defaults, overridden by the reference's own ``TOL`` (a deeper net
    accumulates more bf16 rounding; the reason is written there)."""
    if rehearse:
        return dict(TOL_REHEARSE)
    return {**TOL, **getattr(ref, "TOL", {})}

def center_crop_mean(u8: np.ndarray, crop: int, mean) -> np.ndarray:
    """The published TEST transform, in numpy: mean-subtract, centre crop."""
    _, _, h, w = u8.shape
    ho, wo = (h - crop) // 2, (w - crop) // 2
    x = u8[:, :, ho:ho + crop, wo:wo + crop].astype(np.float32)
    return x - np.asarray(mean, np.float32)[None, :, None, None]


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def dropout_masks(net, variables, n: int, rng) -> dict:
    """Observe the program's dropout keep masks for a batch of ``n`` under
    ``rng``: run each Dropout layer alone on ones (``start=end=layer``;
    the layer's key depends only on ``rng`` and its position)."""
    import jax.numpy as jnp

    info = net.blob_info()
    masks = {}
    for layer in net.layers:
        if getattr(layer, "TYPE", "") != "Dropout":
            continue
        bottom, top = layer.bottoms[0], layer.tops[0]
        shape = (n,) + tuple(info[bottom].shape[1:])
        blobs, _, _ = net.apply(
            variables, {bottom: jnp.ones(shape, jnp.float32)}, rng=rng,
            start=layer.name, end=layer.name)
        masks[layer.name] = blobs[top] > 0
    return masks


class Reference:
    """One configuration's plain reference, jitted once per batch shape."""

    def __init__(self, ref):
        import jax

        from benchmarks.harness.plain_ops import softmax_loss

        self.ref = ref

        def loss_fn(params, x, y, masks):
            logits = ref.forward(params, x, masks)
            return softmax_loss(logits, y), logits

        def run(params, x, y, masks):
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(loss_fn, has_aux=True)(
                    params, x, y, masks)

        self._run = jax.jit(run)

    def grads(self, params, x, y, masks):
        (loss, logits), g = self._run(params, x, y, masks)
        return loss, logits, g

    def sgd(self, params, grads, hist):
        """Caffe SGD on every leaf with the published multipliers."""
        from benchmarks.harness.plain_ops import caffe_sgd

        s = self.ref.SOLVER
        new_p, new_h = {}, {}
        for layer, plist in params.items():
            new_p[layer], new_h[layer] = [], []
            for i, w in enumerate(plist):
                lr_mult, decay_mult = self.ref.multipliers(layer, i)
                w2, h2 = caffe_sgd(
                    w, grads[layer][i], hist[layer][i], lr=s["lr"],
                    momentum=s["momentum"], weight_decay=s["weight_decay"],
                    lr_mult=lr_mult, decay_mult=decay_mult)
                new_p[layer].append(w2)
                new_h[layer].append(h2)
        return new_p, new_h


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def check_step(solver, reference: Reference, x: np.ndarray, y: np.ndarray,
               tol: dict) -> tuple[dict, list[str]]:
    """(a) + (b) on the sample ``x`` (f32, already cropped) / ``y``."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.common import step_key

    ref = reference.ref
    net = solver.train_net
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    rng0 = step_key(key, 0)
    n = x.shape[0]
    feeds = {"data": jnp.asarray(x), "label": jnp.asarray(y)}
    p0 = _f32(variables.params)
    hist0 = jax.tree_util.tree_map(jnp.zeros_like, p0)
    masks = dropout_masks(net, variables, n, rng0)
    facts: dict = {}
    bad: list[str] = []

    def hold(name, value, limit):
        facts[name] = value
        if not (value <= limit):
            bad.append(f"{name} {value:.3g} > {limit:g}")

    # (a) loss and logits of step 0, training mode
    v1, _, loss = fn(variables, slots, 0, feeds, key)
    logits = jax.jit(
        lambda v, f, k: net.apply(v, f, rng=k)[0][ref.LOGITS]
    )(variables, feeds, rng0)
    loss_ref, logits_ref, g = reference.grads(p0, feeds["data"],
                                              feeds["label"], masks)
    loss, loss_ref = float(loss), float(loss_ref)
    facts["loss"], facts["loss_ref"] = loss, loss_ref
    hold("loss_rel", abs(loss - loss_ref) / abs(loss_ref), tol["loss_rel"])
    lg, lr_ = np.asarray(logits, np.float32), np.asarray(logits_ref)
    hold("logits_rel", float(np.abs(lg - lr_).max() / np.abs(lr_).max()),
         tol["logits_rel"])

    # (b) first-step update of the two leaves
    p1_ref, _ = reference.sgd(p0, g, hist0)
    for (layer, i), limit in zip(ref.LEAVES, (tol["update_rel"],
                                              tol["update_rel_last"])):
        d_prog = np.asarray(v1.params[layer][i], np.float32) - np.asarray(p0[layer][i])
        d_ref = np.asarray(p1_ref[layer][i]) - np.asarray(p0[layer][i])
        hold(f"update_rel.{layer}.{i}", _rel(d_prog, d_ref), limit)

    if getattr(ref, "ZERO_BATCH_EXACT", False):
        zfeeds = {"data": jnp.zeros_like(feeds["data"]), "label": feeds["label"]}
        vz, _, _ = fn(variables, slots, 0, zfeeds, key)
        _, _, gz = reference.grads(p0, zfeeds["data"], zfeeds["label"], masks)
        pz_ref, _ = reference.sgd(p0, gz, hist0)
        layer, i = ref.LEAVES[0]
        d_prog = np.asarray(vz.params[layer][i], np.float32) - np.asarray(p0[layer][i])
        d_ref = np.asarray(pz_ref[layer][i]) - np.asarray(p0[layer][i])
        hold(f"decay_exact_rel.{layer}.{i}", _rel(d_prog, d_ref),
             tol["decay_exact_rel"])
    return facts, bad


def check_tau_round(solver, reference: Reference, make_trainer, x, y,
                    tau: int, tol: dict) -> tuple[dict, list[str]]:
    """(c): one ``tau``-step averaging round of the program's trainer on
    fixed f32 feeds, from the solver's initial state.

    ``x``/``y`` hold ``tau * workers * n`` samples; slot t of worker w
    takes rows ``[(t*W + w)*n, (t*W + w + 1)*n)``, the layout
    ``cli._stack_tau`` packs.  The reference runs every worker's ``tau``
    local Caffe-SGD steps (all leaves, momentum carried) and averages.
    Dropout keys follow the trainer's own rule: worker key
    ``fold_in(key, w)``, step key ``step_key(worker key, t)``."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.common import step_key

    ref = reference.ref
    trainer = make_trainer(tau)
    W = trainer.num_workers
    n = x.shape[0] // (tau * W)
    feeds = {
        "data": x[:tau * W * n].reshape(tau, W * n, *x.shape[1:]),
        "label": y[:tau * W * n].reshape(tau, W * n),
    }
    p0 = _f32(solver.variables.params)
    key = solver._key
    trainer.train_round(lambda it: feeds)

    facts: dict = {"workers": W, "tau": tau, "per_worker_batch": n}
    bad: list[str] = []
    same = jax.jit(lambda v: jax.tree_util.tree_map(
        lambda a: jnp.all(a == a[:1]), v))(trainer.variables.params)
    differing = [ln for ln, pl in same.items() if not all(bool(b) for b in pl)]
    facts["replicas_identical"] = not differing
    if differing:
        bad.append(f"replicas differ after the average: {differing[:4]}")

    finals = []
    for w in range(W):
        wkey = jax.random.fold_in(key, w)
        p = p0
        hist = jax.tree_util.tree_map(jnp.zeros_like, p0)
        for t in range(tau):
            rows = slice((t * W + w) * n, (t * W + w + 1) * n)
            masks = dropout_masks(solver.train_net, solver.variables, n,
                                  step_key(wkey, t))
            _, _, g = reference.grads(p, jnp.asarray(x[rows]),
                                      jnp.asarray(y[rows]), masks)
            p, hist = reference.sgd(p, g, hist)
        finals.append(p)
    for (layer, i), limit in zip(ref.LEAVES, (tol["update_rel"],
                                              tol["update_rel_last"])):
        mean_ref = np.mean([np.asarray(f[layer][i]) for f in finals], axis=0)
        prog = np.asarray(trainer.variables.params[layer][i][0], np.float32)
        base = np.asarray(p0[layer][i])
        value = _rel(prog - base, mean_ref - base)
        facts[f"tau_update_rel.{layer}.{i}"] = value
        if not (value <= limit):
            bad.append(f"tau_update_rel.{layer}.{i} {value:.3g} > {limit:g}")
    return facts, bad
