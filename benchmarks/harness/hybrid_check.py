"""The comparison that decides ``correct`` in the hybrid decoder cells
(``phi4flash-solo-s2048``).

Outside the timed window, on ONE seeded sequence at the configuration's
own widths and the timed sizes, the program (bf16 matmuls and
activations; f32 parameters, scan state, Δ, softplus and exponential,
LayerNorm and sub-norm statistics, lambda, the cross-entropy and AdamW
state) is held to the plain reference (``benchmarks/reference/
phi4_flash.py``: f32 at ``highest`` matmul precision, from the solver's own
initial parameters, given the same kept layers and rows of the
vocabulary):

(a) the loss (the prototxt's ``loss`` top);
(b) the logits of the last 256 positions against the reference's full
    forward (rel-L2);
(c) the first AdamW step's change of five leaves against the reference's
    gradients put through the reference's clip and AdamW rule, the
    program's side being ONE step of the solver's own compiled step
    (``Solver.step``: the timed executable, donation and all), which
    leaves the run one iteration on:
      ``a_log``      the memory layer's A_log (its gradient arrives through
                     the layer's own gate AND through every gated memory
                     unit that reads the memory);
      ``dt_bias``    the same layer's b_dt (through softplus, the
                     discretisation and the scan's backward);
      ``kv_rows``    the key and value rows of the full layer's W_qkv
                     (through its own core AND every cross-attention's);
      ``lambda_q1``  one lambda vector of the window layer;
      ``final_norm`` the final LayerNorm's weight.
    rel-L2 of the change over the TENTH of the leaf's entries whose
    reference gradient is largest: the first Adam step is ~lr * sign(g),
    an entry whose gradient bf16 noise can carry across zero is a coin
    and reads 2 lr when it falls the other way, so a share f of flipped
    signs reads 2 sqrt(f).  ``update_rel_half.*`` (the larger half) and
    ``update_rel_all.*`` are reported.
The job adds: every fenced loss finite, zero compiles in the window.

The job's loop (``jobs/lm_decoder_solo.py``) also calls two routing hooks
on its check module; this model has no router, so ``settle_bias`` and
``routing_now`` are stated no-ops here.

Each limit is set from two readings, both on the chip (my chip runs,
PR 32; PERF.md section 6): the largest value the program gave over its
11 seeds, and what the reference itself gives when EVERYTHING is computed in
bf16, the scan's state, Δ and exponential included
(``run_reference(dtype=bfloat16)``: the nearest precision below the
configuration's; ``scratch/hybrid_readings.py``, 2 seeds), which has to
come out as not correct.  It does, on both seeds, by the change of
A_log: a bf16 scan loses the small decays (exp(Δ A) near 1 has 8 bits)
that the gradient of A is made of.

* loss, |rel| <= 3e-4 (program 5e-6 to 8.8e-5; all-bf16 4.8e-5 and
  1.45e-4 on its two seeds, whose losses 10.6245 and 10.6235 happen to lie
  beside the bf16 value 10.625: a bf16 mean of a bf16 log-softmax is a
  multiple of 0.0625 there, and reads 9e-4 to 1.1e-3 on the seeds whose
  loss is 10.634 to 10.636).  A dropped lambda term or a head tied the
  wrong way round moves it by 1e-2 and more.
* logits of the last 256 positions, rel-L2 <= 4.5e-2 (program 2.31e-2 to
  2.48e-2; all-bf16 2.51e-2 and 2.68e-2).  As in ``lm_check.py`` and
  ``decoder_check.py`` this does NOT separate the two (bf16 keeps 8
  mantissa bits through six layers either way, and the tied head reads
  a hidden state of 2,560 at logits of unit variance); it sits at under
  twice the largest reading and holds a wrong pairing, window edge or
  memory (1e-1 and more).
* ``update_rel.a_log`` <= 6e-3 (program 2.2e-3 to 2.7e-3; all-bf16
  1.07e-2 and 1.62e-2): THE limit that tells f32 scan arithmetic from
  bf16.  Over the larger half the program reads 1.2e-2 to 1.7e-2, over
  all entries 6.1e-2 to 6.5e-2: states whose A is large decay at once
  and their gradient is rounding.
* ``update_rel.dt_bias`` <= 2e-3 (program 5.4e-4 to 6.9e-4; all-bf16
  6.6e-4 and 7.6e-4: no separation over the largest tenth, 3.7e-2
  against 3e-3 to 5e-3 over the larger half).  One flipped sign among
  the 512 entries reads 8.8e-2, a bias that is not updated 1.0.
* ``update_rel.kv_rows`` <= 1e-3 (program 4e-6 to 1.7e-5; all-bf16 the
  same: the largest tenth of 6.5 M gradients are far from zero and the
  first Adam step is lr * sign(g) there).  Rows that get only their own
  core's gradient and not the cross-attention's read ~1: the sign of the
  sum is not the sign of a part.
* ``update_rel.lambda_q1`` <= 1e-3 (program 0 to 2e-5 on 7 entries; one
  flipped sign reads 0.76) and ``update_rel.final_norm`` <= 1e-3 (program
  0 to 1.7e-5 on 256 entries; one flipped sign reads 0.125).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.harness.decoder_check import _rel, first_lr

TOL = {
    "loss_rel": 3e-4, "logits_rel": 4.5e-2,
    "update_rel.a_log": 6e-3, "update_rel.dt_bias": 2e-3,
    "update_rel.kv_rows": 1e-3, "update_rel.lambda_q1": 1e-3,
    "update_rel.final_norm": 1e-3,
}
# a CPU rehearsal runs a tiny-width preset on 32 tokens: bf16 noise does
# not average out over so few.  It walks the code; the chip run at the
# published widths is what holds the program.
TOL_REHEARSE = {
    "loss_rel": 2e-2, "logits_rel": 1e-1,
    "update_rel.a_log": 2.0, "update_rel.dt_bias": 2.0,
    "update_rel.kv_rows": 2.0, "update_rel.lambda_q1": 2.0,
    "update_rel.final_norm": 2.0,
}
LAST = 256  # positions whose logits are compared


def tolerances(rehearse: bool = False) -> dict:
    return dict(TOL_REHEARSE if rehearse else TOL)


def settle_bias(solver, forward, feeds, schedule) -> list[float]:
    """No-op: no layer of this model routes, so there is no selection bias
    to level.  One reading of 1.0 (a level load), as the job's log line
    takes it: no forward, no state touched."""
    return [1.0]


def routing_now(solver, config: dict) -> None:
    """No-op: no expert layer, nothing to read at a fence."""
    return None


def reference_config(config: dict) -> dict:
    """The sizes ``reference/phi4_flash.py`` takes, from a configuration
    file."""
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "eps": config["layer_norm_eps"],
            "window": config["sliding_window"],
            "layers": config["num_hidden_layers_published"],
            "mb_per_layer": config["mb_per_layer"],
            "kept": tuple(config["kept_layers"])}


def leaves(config: dict) -> dict:
    """name -> (layer, blob, first row): the memory layer's A_log and b_dt,
    the full layer's key and value rows of W_qkv (from row H·D on), the
    first kept window layer's lambda_q1, the final LayerNorm's weight."""
    half = config["num_hidden_layers_published"] // 2
    window = next(i for i in config["kept_layers"]
                  if i < half and i % config["mb_per_layer"])
    return {"a_log": (f"mamba{half}", 6, 0),
            "dt_bias": (f"mamba{half}", 5, 0),
            "kv_rows": (f"attn{half + 1}", 0, config["hidden_size"]),
            "lambda_q1": (f"attn{window}", 2, 0),
            "final_norm": ("norm_f", 0, 0)}


def _adamw_changes(ref, params, grads, solver_cfg, which: dict):
    """The first AdamW step's change of the leaves ``which``, from ALL
    the gradients (the clip is global)."""
    scale = ref.clip_scale(grads, solver_cfg.clip_gradients)
    lr = first_lr(solver_cfg)
    out = {}
    for name, (layer, i, row) in which.items():
        w0 = params[layer][i][row:]
        w1, _, _ = ref.adamw_step(
            w0, grads[layer][i][row:] * scale, 0.0, 0.0, 1, lr=lr,
            beta1=solver_cfg.momentum, beta2=solver_cfg.momentum2,
            eps=solver_cfg.delta, weight_decay=solver_cfg.weight_decay)
        out[name] = w1 - w0
    return out


def run_reference(ref, params, ids, labels, rcfg, solver_cfg, which,
                  dtype=None):
    """One jitted program: the reference's loss, last logits, the leaves'
    gradients and their first AdamW change.  ``dtype=bfloat16`` is the
    reading below (module docstring); None is the reference proper."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32

    def go(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            (loss, logits), g = jax.value_and_grad(ref.loss, has_aux=True)(
                params, ids, labels, rcfg, dtype)
        g = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), g)
        return {"loss": loss,
                "logits": logits[:, -LAST:].astype(jnp.float32),
                "change": _adamw_changes(ref, params, g, solver_cfg, which),
                "grad": {name: g[layer][i][row:]
                         for name, (layer, i, row) in which.items()}}

    return jax.jit(go)(params, ids, labels)


def forward_program(solver):
    """One jitted program around the solver's own net: the loss and the
    last logits.  Returns ``forward(variables, feeds)``."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.common import step_key

    net = solver.train_net
    key = solver.jitted_train_step()[3]  # the solver's own root key

    def go(variables, feeds, key):
        blobs, _, _ = net.apply(variables, feeds, rng=step_key(key, 0))
        return {"loss": blobs["loss"],
                "logits": blobs["lm_head"][:, -LAST:].astype(jnp.float32)}

    go = jax.jit(go)
    return lambda variables, feeds: go(variables, feeds, key)


def run_program(solver, forward, ids, labels, which):
    """The same quantities as ``run_reference`` from the program: the
    forward's facts from ``forward``, then ONE step of the solver's own
    compiled step on the same sequence for the leaves' change.  The
    solver is left one iteration on, as a run that began with this
    sequence would be."""
    import jax
    import jax.numpy as jnp

    feeds = {"data": jnp.asarray(ids), "label": jnp.asarray(labels)}
    got = jax.tree_util.tree_map(
        np.asarray, forward(solver.variables, feeds))
    leaf = lambda l, i, row: np.asarray(solver.variables.params[l][i])[row:]
    before = {name: leaf(*spec) for name, spec in which.items()}
    solver.step(1, lambda it: feeds)
    got["change"] = {name: leaf(*spec) - before[name]
                     for name, spec in which.items()}
    return got


def compare(got: dict, want: dict) -> dict:
    """The facts (a) to (c) of ``got`` against the reference ``want``."""
    g, w = float(got["loss"]), float(want["loss"])
    facts = {"loss": g, "loss_ref": w, "loss_rel": abs(g - w) / abs(w),
             "logits_rel": _rel(got["logits"], want["logits"])}
    for name in got["change"]:
        size = np.abs(np.asarray(want["grad"][name]))
        masks = {"_all": np.ones(size.shape, bool)}
        for part, q in (("", 0.9), ("_half", 0.5)):
            masks[part] = size >= np.quantile(size, q)
        for part, sure in masks.items():
            facts[f"update_rel{part}.{name}"] = _rel(
                got["change"][name][sure], want["change"][name][sure])
        facts[f"update_entries.{name}"] = int(masks[""].sum())
    return facts


def check_step(solver, ref, config: dict, ids, labels, tol: dict, forward,
               want=None):
    """(facts, problems) of the program against the reference on the
    sequences ``ids`` / ``labels`` ([n, S] int32).  Steps the solver once
    (``run_program``).  ``forward``: the solver's ``forward_program``;
    ``want``: a reference run the caller already made from the solver's
    present state (``scratch/hybrid_readings.py``)."""
    import jax
    import jax.numpy as jnp

    which = leaves(config)
    t0 = time.perf_counter()
    if want is None:
        want = run_reference(
            ref, solver.variables.params, jnp.asarray(ids),
            jnp.asarray(labels), reference_config(config), solver.config,
            which)
        want = jax.tree_util.tree_map(np.asarray, want)
    t1 = time.perf_counter()
    got = run_program(solver, forward, ids, labels, which)
    facts = compare(got, want)
    facts["reference_s"] = round(t1 - t0, 1)
    facts["program_s"] = round(time.perf_counter() - t1, 1)
    problems = [f"{name} {facts[name]:.3g} > {limit:g}"
                for name, limit in tol.items() if not facts[name] <= limit]
    return facts, problems
