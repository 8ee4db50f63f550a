"""The comparison that decides ``correct`` in the window-and-full attention
decoder cell (``laguna-solo-s8192``).

Outside the timed window, on ONE seeded sequence at the configuration's
own widths and the timed sizes (1 x 8,192 tokens), the program (bf16
matmuls and activations; f32 parameters, gradients, AdamW moments, the
rotary tables and their product, norm statistics, router scores and
selection, the cross-entropy) is held to the plain reference
(``benchmarks/reference/laguna.py``: f32 at ``highest`` matmul precision,
a head's scores materialised under an explicit boolean mask, YaRN written
out, walked on the host one block at a time, from the solver's own
initial parameters, given the same share of the experts and of the
vocabulary).  ``linear_check.py``'s comparison (its ``compare``: (a) the
loss terms, the TOTAL from ONE step of the solver's own compiled step;
(b) the last 256 logits over the routing-agreeing positions; (c) routing
near-ties; (d) the first AdamW step's change of chosen leaves on the
tenth of their entries whose reference gradient is largest), with this
configuration's leaves and one more kind of reading:

(e) ``mixed_rel.full`` / ``mixed_rel.window``: the OUTPUT of the first
    full attention layer (``attn0``: 48 heads, YaRN over half a head) and
    of the first sliding one (``attn1``: 64 heads, window 512, plain RoPE
    over a whole head), [S, D] before the residual, rel-L2 over all 8,192
    positions against the reference's.  Another frequency table or
    another factor on cos and sin moves the full layer's output by a
    third of its norm and more; the readings (a) to (d) see it far less.
(f) ``window_edge``: a mask one key off is NOT seen by (e): the tokens'
    values share a mean, so one key in 512 moves a sliding layer's output
    by 0.56 % of its norm, under the layer's own bf16 noise (0.9 %), and
    reaches the logits and the large gradients' signs not at all.  But it
    moves the output in a KNOWN direction: the reference computes the
    first sliding layer's output under a window one key narrower and one
    key wider too, and the reading is the larger of the two coefficients
    |<y - y_ref, d>| / <d, d>, d = y_ref(window -+ 1) - y_ref(window): the
    part of the program's departure that lies along what that wrong
    window would do.  Noise has no part along one direction among 16 M
    (~1e-3); a program whose window is 511 or 513 reads 1.

The leaves of (d):
  ``gate_w``      W_g of the first sliding layer (64 x 2048: the head-wise
                  gate);
  ``full_qk``     W_q and W_k of the last full layer (``attn4``): the
                  projections YaRN turns;
  ``full_out``    its W_o;  ``window_out``  the last sliding layer's W_o;
  ``window_kv``   W_k and W_v of the first sliding layer (8 heads that 64
                  query heads read);
  ``dense_up``    W_up of the dense MLP;
  ``router``      the last expert layer's router;
  ``held_gate``   its held experts' W_gate (all 16: one expert gets ~256
                  rows of a sequence);
  ``final_norm``  the final RMSNorm's weight.
No limit is read over fewer than 64 entries.  The job adds: every fenced
loss finite, zero compiles in the window.

The job's loop (``jobs/lm_decoder_solo.py``) also calls two routing hooks
on its check module, ``linear_check.py``'s: ``settle_bias`` is a stated
no-op (these routers select with no bias: there is none to level),
``routing_now`` reads the expert layers' counters after a fence.

WHAT THE LOWER PRECISION IS.  ``run_reference(dtype=bfloat16)`` computes
everything in bf16: forward and backward (the rotary product, the scores
and their softmax, the gates, norm statistics, the router's sigmoid, the
cross-entropy) AND the parameters, the gradients, both AdamW moments and
the step's arithmetic, its new weight leaving as a bf16 number (a program
WITHOUT f32 master weights).  A weight near 0.02 then moves in steps of
1.2e-4 where the first change is 3e-4.

WHICH WRONG PROGRAM EACH LIMIT REFUSES (shown on the CPU preset by
``tests/test_laguna.py``; the chip's readings are in PERF.md section 2):
  bf16 master weights        every ``update_rel.*`` of a matrix (0.125)
  a window of 511 or 513     ``window_edge``
  plain RoPE for YaRN        ``mixed_rel.full``
  ``attention_factor`` 1     ``mixed_rel.full``
  a dropped or D-wide gate   ``mixed_rel.*``, ``update_rel.gate_w``
  an unscaled / unnormalised router   ``main_rel``, ``aux_rel``, ``tie_gap``

The limits, from two readings on the chip at the published widths (my
chip runs, PR 50; PERF.md section 2): the program's over 10 fresh seeds
(5000000101-104 and 201-205, 2147483749: the cell's own runs and
``scratch/hybrid_readings.py --workload laguna-solo-s8192``) and the
all-bf16 reading on three (102-104): NOT correct on any, nine to eleven
limits broken on each.  What four wrong programs read is
``scratch/window_readings.py``'s: the f32 reference with the one setting
changed against the f32 reference proper (seeds 102 and 104).  The
check's last line holds every reading beside its limit and names what
failed.

* total, main and auxiliary loss, |rel| <= 2.3e-4, 1.4e-4 and 1e-3
  (program 3.6e-6 to 1.08e-4, 3.3e-6 to 6.6e-5 and 2.3e-6 to 3.0e-4;
  all-bf16 4.9e-4 to 4.5e-3, 2.8e-4 to 3.0e-3 and 1.5e-3 to 1.7e-3).
  The first two are the geometric middle of their two readings: 2.1 x
  above the largest sound reading, 2.0 to 2.1 x below the control's
  smallest, so every all-bf16 seed fails all three.  The total is the
  timed step's own; the auxiliary sum (5,460 at initialisation) follows
  the routing's counts.  A router left unscaled or unnormalised moves
  the main loss by 1e-2 and more.
* logits of the last 256 positions, rel-L2 over the routing-agreeing
  positions <= 3e-2 (program 1.15e-2 to 1.20e-2 over 152 to 177 of the
  256; all-bf16 1.32e-2 to 1.43e-2: no separation, as in every decoder
  cell).  3,670 to 4,030 of the 32,768 (token, layer) top-8 sets differ.
* near-tie limit (c), (p_k - p_{k+1}) / p_k <= 0.1 (program 0.021 to
  0.037; all-bf16 0.022 to 0.032: the precision does not move it).  Its
  upper reading is a wrong ROUTER: one scored with other weights reads
  ~1.
* ``mixed_rel.full`` and ``mixed_rel.window`` <= 2e-2 (program 6.85e-3 to
  7.09e-3 and 8.3e-3 to 9.4e-3: the layers' own bf16 noise; all-bf16
  8.7e-3 to 8.9e-3 and 9.9e-3 to 1.11e-2).  Plain RoPE where YaRN belongs
  reads 0.478 in ``mixed_rel.full``, ``attention_factor`` 1 reads 0.366
  (and 0.58 / 0.50 in the sliding layer behind it); a window of 511 or
  513 reads 5.6e-3 to 5.8e-3 in ``mixed_rel.window`` as f32 against f32,
  so ~1.07e-2 behind the program's noise: INSIDE the limit, which is why
  (f) exists.
* ``window_edge`` <= 0.5 (program 4.2e-4 to 1.9e-3, all-bf16 2.0e-3; a
  window of 511 or 513 reads 1.000; the two wrong rotary programs 0.24
  to 0.30: their departure is large and has a part along every
  direction).
* ``update_rel.gate_w`` <= 5e-2 over 13,108 entries (program 1.15e-6 to
  1.43e-6, no flipped sign on any seed; ONE flipped sign reads 1.75e-2,
  so the limit holds eight; all-bf16 0.124 to 0.126).
* ``update_rel.full_qk``, ``.full_out``, ``.window_out``, ``.window_kv``,
  ``.dense_up`` <= 1e-2 over 419,431 to 1,677,723 entries (program 2.4e-5
  to 3.3e-5 for ``full_qk``, 1.26e-6 to 2.16e-6 for the others, no
  flipped sign on any seed; one reads 1.5e-3 to 3.1e-3; all-bf16 0.125 to
  0.126 on all five, every seed: the stored weight's rounding).
* ``update_rel.router`` <= 5e-2 (program 1.5e-6 to 1.9e-6 over 48,948
  entries, no flipped sign: the largest gradients are the collapsed
  columns'; all-bf16 0.125 to 0.126).
* ``update_rel.held_gate`` <= 0.3 (program 8.3e-3 to 9.3e-2: 33 to
  4,510 flipped signs among 1.47 M to 1.68 M entries; all-bf16 0.122 to
  0.145: NO separation).  One sequence's sigmoid routers collapse (the
  fullest expert takes every token of its layer), a tenth of the tokens
  is routed otherwise than in the reference, and the held experts see
  lumps of 0 to 10 % of the pairs: this limit holds the mathematics (an
  expert that is not updated reads 1.0, another expert's rows ~1.4), not
  the precision, which six other leaves hold.
* ``update_rel.final_norm`` <= 0.25 over 205 entries (program 0 on every
  seed; all-bf16 1.0: a weight of one cannot move by 3e-4 in bf16).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.harness.decoder_check import _rel, first_lr
from benchmarks.harness.linear_check import (  # noqa: F401
    LAST, _adamw_changes, _leaf, compare as compare_step, expert_layers,
    routing_now, run_program, settle_bias)

TOL = {
    "total_rel": 2.3e-4, "main_rel": 1.4e-4, "aux_rel": 1e-3,
    "logits_rel": 3e-2, "tie_gap": 0.1,
    "mixed_rel.full": 2e-2, "mixed_rel.window": 2e-2, "window_edge": 0.5,
    "update_rel.gate_w": 5e-2, "update_rel.full_qk": 1e-2,
    "update_rel.full_out": 1e-2, "update_rel.window_out": 1e-2,
    "update_rel.window_kv": 1e-2, "update_rel.dense_up": 1e-2,
    "update_rel.router": 5e-2, "update_rel.held_gate": 0.3,
    "update_rel.final_norm": 0.25,
}
# a CPU rehearsal runs a tiny-width preset on 32 tokens: bf16 noise does
# not average out over so few.  It walks the code; the chip run at the
# published widths is what holds the program.
TOL_REHEARSE = {
    **{name: 2.0 for name in TOL},
    "total_rel": 2e-2, "main_rel": 2e-2, "aux_rel": 1e-1, "logits_rel": 1e-1,
    "tie_gap": 1.0, "mixed_rel.full": 1e-1, "mixed_rel.window": 1e-1,
    "window_edge": 0.5,
}
MIXED = {"full": "full_attention", "window": "sliding_attention"}


def tolerances(rehearse: bool = False) -> dict:
    return dict(TOL_REHEARSE if rehearse else TOL)


def _first(config: dict, kind: str, last: bool = False) -> int:
    """The first (or last) block of ``kind`` in the configuration's cut."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    hits = [i for i, k in enumerate(kinds) if k == kind]
    return hits[-1] if last else hits[0]


def mixed_layers(config: dict) -> dict:
    """reading -> the attention layer whose output it compares."""
    return {name: f"attn{_first(config, kind)}"
            for name, kind in MIXED.items()}


def reference_config(config: dict) -> dict:
    """The sizes ``reference/laguna.py`` takes, from a configuration
    file; ``mixed_readings`` is this module's own (``run_reference``)."""
    n = config["num_hidden_layers"]
    ropes = {k: v for k, v in config["rope_parameters"].items()
             if isinstance(v, dict)}
    return {"kinds": tuple(config["layer_types"][:n]),
            "heads": tuple(config["num_attention_heads_per_layer"][:n]),
            "dense": tuple(t == "dense"
                           for t in config["mlp_layer_types"][:n]),
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "window": config["sliding_window"],
            "ropes": tuple((kind, tuple(sorted(group.items())))
                           for kind, group in sorted(ropes.items())),
            "eps": config["rms_norm_eps"],
            "top_k": config["num_experts_per_tok"],
            "scale": config["moe_routed_scaling_factor"],
            "first_expert": config["first_expert"],
            "layers": n,
            "aux_coef": config["router_aux_loss_coef"],
            "mixed_readings": tuple(mixed_layers(config).items())}


def leaves(config: dict) -> dict:
    """name -> ((layer, blob, rows), ...): the parts a leaf is read from
    (``linear_check._leaf``), raveled and joined."""
    n = config["num_hidden_layers"]
    full = f"attn{_first(config, 'full_attention', last=True)}"
    window = f"attn{_first(config, 'sliding_attention')}"
    window_last = f"attn{_first(config, 'sliding_attention', last=True)}"
    dense = next(i for i in range(n)
                 if config["mlp_layer_types"][i] == "dense")
    moe = max(i for i in range(n) if config["mlp_layer_types"][i] != "dense")
    return {
        "gate_w": ((window, 4, None),),
        "full_qk": ((full, 0, None), (full, 1, None)),
        "full_out": ((full, 3, None),),
        "window_out": ((window_last, 3, None),),
        "window_kv": ((window, 1, None), (window, 2, None)),
        "dense_up": ((f"mlp{dense}", 1, None),),
        "router": ((f"moe{moe}", 0, None),),
        "held_gate": ((f"moe{moe}", 1, None),),
        "final_norm": (("norm_f", 0, None),),
    }


def window_edges(ref, params, ids, rcfg) -> dict:
    """{"minus", "plus"}: the first sliding layer's output [B, S, D] under
    a window one key narrower / wider, less its output under the window
    itself, from the reference in f32: the directions a program with the
    wrong edge departs in (reading (f))."""
    import jax
    import jax.numpy as jnp

    layer = dict(rcfg["mixed_readings"])["window"]
    i = int(layer[len("attn"):])
    with jax.default_matmul_precision("highest"):
        x = ref.block_input(params, ids, rcfg, i)
        h = ref.rms_norm(x, params[f"norm{i}a"][0], rcfg["eps"])
        out = lambda window: jax.jit(lambda p, h: jnp.stack([
            ref.attention(p, row, rcfg["heads"][i], rcfg["kinds"][i],
                          {**rcfg, "window": window}) for row in h]))(
            params[layer], h)
        base = out(rcfg["window"])
        return {"minus": out(rcfg["window"] - 1) - base,
                "plus": out(rcfg["window"] + 1) - base}


def run_reference(ref, params, ids, labels, rcfg, solver_cfg, which,
                  dtype=None):
    """The reference's loss terms, last logits, score and chosen experts
    per expert layer, the outputs of the two attention layers the check
    reads (and, from the reference proper, ``window_edges``), the leaves'
    gradients and the leaves before and after their first AdamW step.  ``dtype=bfloat16`` is the reading below (module
    docstring); None is the reference proper.  The reference's by-block
    walk, and one small program for the update."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype or jnp.float32)
    with jax.default_matmul_precision("highest"):
        (total, ((main, aux), (logits, routing, mixed))), g = (
            ref.loss_and_grads_by_block(params, ids, labels, rcfg, dtype))
    rule = dict(clip=solver_cfg.clip_gradients, lr=first_lr(solver_cfg),
                beta1=solver_cfg.momentum, beta2=solver_cfg.momentum2,
                eps=solver_cfg.delta, weight_decay=solver_cfg.weight_decay)
    specs = tuple(which.items())
    before, after = jax.jit(_adamw_changes, static_argnums=(0, 3, 4, 5))(
        ref, params, g, tuple(rule.items()), specs, dtype.name)
    edges = (window_edges(ref, params, ids, rcfg)
             if dtype == jnp.float32 else {})
    return {"total": total, "main": main.astype(jnp.float32),
            "aux": aux.astype(jnp.float32),
            "logits": logits[:, -LAST:].astype(jnp.float32),
            "scores": {n: s.astype(jnp.float32)
                       for n, (s, _) in routing.items()},
            "chosen": {n: c for n, (_, c) in routing.items()},
            "mixed": {reading: mixed[layer].astype(jnp.float32)
                      for reading, layer in rcfg["mixed_readings"]},
            "edges": edges,
            "before": before, "after": after,
            "grad": {name: _leaf(g, spec, jnp) for name, spec in specs}}


def forward_program(solver):
    """One jitted program around the solver's own net: the loss terms, the
    last logits, every expert layer's routing (the layer's own ``route``
    on the layer's own input) and its counter, and the output of the
    first attention layer of each kind.  Returns
    ``forward(variables, feeds)``."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.common import get_config, step_key
    from sparknet_tpu.ops.moe import route

    net = solver.train_net
    key = solver.jitted_train_step()[3]  # the solver's own root key
    cdt = get_config().compute_dtype
    layers = expert_layers(net)
    mixers = [l for l in net.layers if l.type == "GatedAttention"]
    keep = {reading: next(l.name for l in mixers
                          if bool(l.window) == (reading == "window"))
            for reading in MIXED}

    def go(variables, feeds, key):
        blobs, state, total = net.apply(variables, feeds,
                                        rng=step_key(key, 0))
        chosen = {}
        for l in layers:
            x = blobs[l.bottoms[0]]
            chosen[l.name] = route(
                variables.params[l.name][0].astype(cdt),
                x.reshape(-1, x.shape[-1]), l.top_k, l.norm_topk_prob,
                scoring=l.scoring, scale=l.scale)[3]
        return {"total": total, "main": blobs["loss"],
                "aux": sum(blobs[l.tops[1]] for l in layers),
                "logits": blobs["lm_head"][:, -LAST:].astype(jnp.float32),
                "chosen": chosen,
                "mixed": {reading: blobs[layer].astype(jnp.float32)
                          for reading, layer in keep.items()},
                "load": {l.name: state[l.name]["load"] for l in layers}}

    go = jax.jit(go)
    return lambda variables, feeds: go(variables, feeds, key)


def mixed_facts(got: dict, want: dict, edges: dict) -> dict:
    """The readings (e) and (f) of the attention outputs ``got`` against
    the reference's ``want`` ({"full", "window"}: [B, S, D]) and its
    ``window_edges``."""
    facts = {f"mixed_rel.{reading}": _rel(got[reading], out)
             for reading, out in want.items()}
    off = np.asarray(got["window"], np.float64) - want["window"]
    along = [abs(float(np.sum(off * d)) / float(np.sum(d * d)))
             for d in (np.asarray(e, np.float64) for e in edges.values())
             if d.any()]
    facts["window_edge"] = max(along, default=0.0)
    return facts


def compare(got: dict, want: dict, share: tuple[int, int] = (0, 0)) -> dict:
    """``linear_check.compare``'s facts (a) to (d), then (e) and (f)."""
    return {**compare_step(got, want, share),
            **mixed_facts(got["mixed"], want["mixed"], want["edges"])}


def verdict(facts: dict, tol: dict) -> tuple[str, list[str]]:
    """(every reading beside its limit and the names of what failed, on
    one line; the problems as the job reports them)."""
    failed = [name for name, limit in tol.items()
              if not facts[name] <= limit]
    readings = " ".join(f"{name}={facts[name]:.3g}/{limit:g}"
                        for name, limit in tol.items())
    line = f"window_check readings/limits: {readings}; failed: " + (
        ",".join(failed) or "none")
    return line, [f"{name} {facts[name]:.3g} > {tol[name]:g}"
                  for name in failed]


def check_step(solver, ref, config: dict, ids, labels, tol: dict, forward,
               want=None):
    """(facts, problems) of the program against the reference on the
    sequences ``ids`` / ``labels`` ([n, S] int32).  Steps the solver once
    (``run_program``).  ``forward``: the solver's ``forward_program``;
    ``want``: a reference run the caller already made from the solver's
    present state (``scratch/window_readings.py``)."""
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
    facts = compare(got, want,
                    (config["first_expert"], config["num_experts"]))
    facts["reference_s"] = round(t1 - t0, 1)
    facts["program_s"] = round(time.perf_counter() - t1, 1)
    line, problems = verdict(facts, tol)
    print(line, file=sys.stderr, flush=True)
    return facts, problems
