"""The comparison that decides ``correct`` in the linear-attention decoder
cell (``qwen3next-solo-s4096``).

Outside the timed window, on ONE seeded sequence at the configuration's
own widths and the timed sizes, the program (bf16 matmuls and
activations; f32 parameters, gradients, AdamW moments, the delta state,
its gates and decay, norm statistics, router scores and selection, the
cross-entropy) is held to the plain reference
(``benchmarks/reference/qwen3_next.py``: f32 at ``highest`` matmul
precision, the delta rule a scan over time, walked on the host one block
at a time, from the solver's own initial parameters, given the same share
of the experts and of the vocabulary):

(a) the loss terms: the main cross-entropy and the auxiliary
    (load-balancing) sum, each as the prototxt's tops report it, from a
    forward of the check's own around the solver's net, and the TOTAL as
    ONE step of the solver's own compiled step returns it
    (``Solver.step``: the timed executable, donation and all, which
    leaves the run one iteration on);
(b) the logits of the last 256 positions (rel-L2), over the positions
    whose tokens were routed as the reference routed them in every
    expert layer (a token that a near-tie sent to another expert has
    other logits, and (c) is what holds it; the value over all 256 is
    reported as ``logits_rel_all``);
(c) routing: the number of (token, layer) top-k SETS that differ from
    the reference's is reported, and each such token must be a near-tie
    in the reference: (p_k - p_{k+1}) / p_k no larger than the limit;
(d) the first AdamW step's change, from that one compiled step, against
    the reference's gradients put through the reference's clip and AdamW
    rule, of:
      ``qkvz_k``      the k rows of the first DeltaNet layer's W_qkvz
                      (through the convolution, the normalisation and
                      the rule's state, forward and backward);
      ``gdn_out``     the same layer's W_out;
      ``attn_kv``     W_k and W_v of the attention layer (two heads of
                      256 that sixteen query heads read);
      ``attn_gate``   the gate half of its W_q;
      ``router``      the last expert layer's router;
      ``held_gate``   its held experts' W_gate;
      ``shared_gate`` its shared expert's gate w_g;
      ``final_norm``  the final RMSNorm's weight;
    rel-L2 of the change over the TENTH of the leaf's entries whose
    reference gradient is largest among those that have one: the first
    Adam step is ~lr * sign(g), an entry whose gradient bf16 noise or
    another routing can carry across zero is a coin and reads 2 lr when
    it falls the other way, so a share f of flipped signs reads
    2 sqrt(f).  ``update_rel_half.*`` (the larger half),
    ``update_rel_all.*`` and ``update_flipped.*`` are reported;
      ``decay``       A_log and dt_bias of ALL the DeltaNet layers
                      together (3 x 32 + 3 x 32 entries), read NORM-WISE
                      over all of them (``update_rel_all.decay``), not as
                      the largest of a few entries: one near-zero
                      gradient that flips reads 2 / sqrt(192) = 0.14
                      here, where a limit over seven entries would read
                      0.76 and refuse an innocent program.
    No limit is read over fewer than 64 entries.
The job adds: every fenced loss finite, zero compiles in the window.

The job's loop (``jobs/lm_decoder_solo.py``) also calls two routing hooks
on its check module: ``settle_bias`` is a stated no-op (softmax routing
with an auxiliary loss has no balancing bias to level), ``routing_now``
reads the expert layers' counters after a fence.

WHAT THE LOWER PRECISION IS.  ``run_reference(dtype=bfloat16)`` computes
everything in bf16: the forward and backward (the delta state and its
decay, the gates, norm statistics, the router's softmax, the
cross-entropy) AND the parameters, the gradients, both AdamW moments and
the step's arithmetic, its new weight leaving as a bf16 number (a
program WITHOUT f32 master weights, as ``looped_check.py`` has it).  A
weight near 0.02 then moves in steps of 1.2e-4 where the first change is
3e-4, and A_log (0.1 to 2.8) and dt_bias (-6.9 to -2.2) cannot move by
3e-4 at all.

The limits, from two readings on the chip at the published widths (my
chip runs, PR 47; PERF.md section 2): the program's over 22 fresh seeds
(4700000201-214 by ``scratch/hybrid_readings.py --workload
qwen3next-solo-s4096``; 4700000101 and 301-307, the cell's own runs), and the
all-bf16 reading on six (4700000201-206): NOT correct on any, six limits
broken on all six.  No limit sits at less than twice the program's
largest reading; the check's last line holds every reading beside its
limit and names what failed.

* total, main and auxiliary loss, |rel| <= 3e-4, 3e-4 and 1e-3 (program
  4e-6 to 9.6e-5, 1.3e-5 to 6.1e-5 and 4e-7 to 1.15e-4; all-bf16 8.7e-5 to
  3.0e-3, 2.2e-5 to 2.5e-3 and 2.5e-4 to 3.7e-3: a bf16 mean of a bf16
  log-softmax near 10.2 is a multiple of 0.0625, which tells it five
  times in six and is not what the control rests on).  A gate left out
  or a norm that is not zero-centred moves the loss by 1e-2 and more.
* logits of the last 256 positions, rel-L2 over the routing-agreeing
  positions <= 5.5e-2 (program 2.41e-2 to 2.65e-2 over 77 to 106 of the
  256; all-bf16 3.1e-2 to 5.1e-2: no separation, as in every decoder
  cell; a wrong head grouping, RoPE over the whole head or a chunk
  started from the wrong state reads 1e-1 and more).  3,620 to 3,850 of
  the 16,384 (token, layer) top-10 sets differ: 512 softmax scores near
  1 / 512 lie close together.
* near-tie limit (c), (p_k - p_{k+1}) / p_k <= 0.3 (program 0.077 to 0.15;
  all-bf16 0.108 to 0.141: the fourth layer's router reads a residual
  stream that bf16 has carried through three blocks).  A router scored
  with other weights reads ~1.
* ``update_rel.qkvz_k``, ``.gdn_out``, ``.attn_kv``, ``.attn_gate`` <= 1e-2
  (program 1.75e-6 to 1.81e-6, 1.97e-6 to 2.06e-6, 1.49e-6 to 1.66e-6 and
  4.7e-5 to 5.9e-5 over 419,431, 838,862, 209,716 and 838,862 entries, NO
  flipped sign on any seed; all-bf16 0.124 to 0.126 on all four, every
  seed: the stored weight's rounding).  ONE flipped sign among them
  reads 2.2e-3 to 4.4e-3, so the limit leaves room for a few; a state
  that is not carried across chunks, or keys of the wrong head, read
  ~1.
* ``update_rel.router`` <= 0.5 (program 0.142 to 0.194, 551 to 970 flipped
  signs among 104,858; all-bf16 0.236 to 0.272: a quarter of the tokens
  is routed otherwise than in the reference, so like the logits' this
  limit holds the mathematics and not the precision).  A router that is
  not updated reads 1.0, the auxiliary loss left out ~0.7.
* ``update_rel.held_gate`` <= 8e-2 (program 0.014 to 0.030, 195 to 837
  flipped signs among 3,355,444; all-bf16 0.122 to 0.127 on every seed).
* ``update_rel.shared_gate`` and ``update_rel.final_norm`` <= 0.25 (program
  1.5e-6 to 2.4e-6 and 3.7e-7 to 4.5e-7 on 205 entries each, no flipped
  sign on any seed; all-bf16 0.129 to 0.135 and 1.8e-3: a zero-centred
  weight near 0 moves freely in bf16).  One flipped sign among 205 reads
  0.14 and the limit holds three; a leaf that is not updated reads 1.0.
* ``update_rel_all.decay`` <= 0.8, over ALL 192 entries (program 1e-4 to
  0.397: 0 to 9 flipped signs, each 2 / sqrt(192) = 0.144 and k of them
  2 sqrt(k / 192); all-bf16 0.993 to 1.0 on every seed, 188 to 192 of 192
  signs "flipped": A_log and dt_bias do not move at all).  THE limit that
  tells an f32 decay from a bf16 one; thirty flipped signs would reach
  it.  The same leaves over seven entries, as ``hybrid_check.py`` reads
  Mamba's lambda, would have refused 13 of the first 15 seeds.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.harness.decoder_check import _rel, concentration, first_lr

TOL = {
    "total_rel": 3e-4, "main_rel": 3e-4, "aux_rel": 1e-3,
    "logits_rel": 5.5e-2, "tie_gap": 0.3,
    "update_rel.qkvz_k": 1e-2, "update_rel.gdn_out": 1e-2,
    "update_rel.attn_kv": 1e-2, "update_rel.attn_gate": 1e-2,
    "update_rel.router": 0.5, "update_rel.held_gate": 8e-2,
    "update_rel.shared_gate": 0.25, "update_rel.final_norm": 0.25,
    "update_rel_all.decay": 0.8,
}
# a CPU rehearsal runs a tiny-width preset on 32 tokens: bf16 noise does
# not average out over so few.  It walks the code; the chip run at the
# published widths is what holds the program.
TOL_REHEARSE = {
    "total_rel": 2e-2, "main_rel": 2e-2, "aux_rel": 1e-1,
    "logits_rel": 1e-1, "tie_gap": 1.0,
    "update_rel.qkvz_k": 2.0, "update_rel.gdn_out": 2.0,
    "update_rel.attn_kv": 2.0, "update_rel.attn_gate": 2.0,
    "update_rel.router": 2.0, "update_rel.held_gate": 2.0,
    "update_rel.shared_gate": 2.0, "update_rel.final_norm": 2.0,
    "update_rel_all.decay": 2.0,
}
LAST = 256  # positions whose logits are compared
WHOLE = ("decay",)  # leaves read over ALL their entries (module docstring)


def tolerances(rehearse: bool = False) -> dict:
    return dict(TOL_REHEARSE if rehearse else TOL)


def expert_layers(net) -> list:
    return [l for l in net.layers if l.type == "MoE"]


def settle_bias(solver, forward, feeds, schedule) -> list[float]:
    """No-op: softmax routing with an auxiliary loss selects with no bias,
    so there is none to level.  One reading of 1.0, as the job's log line
    takes it: no forward, no state touched."""
    return [1.0]


def routing_now(solver, config: dict) -> tuple[float, float]:
    """(fullest expert over the mean, % of the pairs on the experts held
    here) of the solver's LAST step, from the expert layers' counters: a
    few KB read after a fence, for the job's log of a window."""
    first, n = config["first_expert"], config["num_experts"]
    load = {l.name: np.asarray(solver.variables.state[l.name]["load"])
            for l in expert_layers(solver.train_net)}
    held = sum(l[first:first + n].sum() for l in load.values())
    return (round(concentration(load), 2),
            round(100.0 * float(held / sum(l.sum() for l in load.values())), 2))


def reference_config(config: dict) -> dict:
    """The sizes ``reference/qwen3_next.py`` takes, from a configuration
    file."""
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "rotary": int(config["head_dim"]
                          * config["partial_rotary_factor"]),
            "theta": float(config["rope_theta"]),
            "eps": config["rms_norm_eps"],
            "lk_heads": config["linear_num_key_heads"],
            "lv_heads": config["linear_num_value_heads"],
            "lk_dim": config["linear_key_head_dim"],
            "lv_dim": config["linear_value_head_dim"],
            "top_k": config["num_experts_per_tok"],
            "first_expert": config["first_expert"],
            "layers": config["num_hidden_layers"],
            "interval": config["full_attention_interval"],
            "aux_coef": config["router_aux_loss_coef"]}


def leaves(config: dict) -> dict:
    """name -> ((layer, blob, rows), ...): the parts a leaf is read from,
    raveled and joined; ``rows`` picks the part of a blob: None the whole
    blob, (start, stop) rows of it (the k rows of W_qkvz), ("gate", H, D)
    the gate half of a W_q viewed [H, 2, D, E]."""
    n = config["num_hidden_layers"]
    every = config["full_attention_interval"]
    gdn = [i for i in range(n) if (i + 1) % every]
    attn = next(i for i in range(n) if (i + 1) % every == 0)
    kw = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    gate = ("gate", config["num_attention_heads"], config["head_dim"])
    return {
        "qkvz_k": ((f"gdn{gdn[0]}", 0, (kw, 2 * kw)),),
        "gdn_out": ((f"gdn{gdn[0]}", 6, None),),
        "attn_kv": ((f"attn{attn}", 1, None), (f"attn{attn}", 2, None)),
        "attn_gate": ((f"attn{attn}", 0, gate),),
        "router": ((f"moe{n - 1}", 0, None),),
        "held_gate": ((f"moe{n - 1}", 1, None),),
        "shared_gate": ((f"moe{n - 1}", 7, None),),
        "final_norm": (("norm_f", 0, None),),
        "decay": tuple((f"gdn{i}", b, None) for i in gdn for b in (3, 4)),
    }


def _leaf(tree, spec, xp):
    """The leaf ``spec`` of ``tree`` as one flat vector of ``xp`` (numpy
    or jax.numpy)."""
    parts = []
    for layer, blob, rows in spec:
        w = xp.asarray(tree[layer][blob])
        if rows is not None and rows[0] == "gate":
            w = w.reshape((rows[1], 2, rows[2]) + w.shape[1:])[:, 1]
        elif rows is not None:
            w = w[rows[0]:rows[1]]
        parts.append(w.reshape(-1))
    return xp.concatenate(parts)


def _adamw_changes(ref, params, grads, rule: tuple, which: tuple, dtype: str):
    """(before, after): the leaves ``which`` either side of the first
    AdamW step, from ALL the gradients (the clip is global), with the
    parameters, the gradients, both moments and the step's arithmetic in
    ``dtype``.  float32 is the reference proper; bfloat16 is a program
    WITHOUT f32 master weights: its new weight is a bf16 number and
    leaves the program as one."""
    import jax
    import jax.numpy as jnp

    rule = dict(rule)
    params, grads = jax.tree_util.tree_map(
        lambda a: a.astype(dtype), (params, grads))
    scale = ref.clip_scale(grads, rule.pop("clip")).astype(dtype)
    before, after = {}, {}
    for name, spec in which:
        before[name] = _leaf(params, spec, jnp)
        after[name] = ref.adamw_step(
            before[name], _leaf(grads, spec, jnp) * scale, 0.0, 0.0, 1,
            **rule)[0].astype(dtype)
    return before, after


def run_reference(ref, params, ids, labels, rcfg, solver_cfg, which,
                  dtype=None):
    """The reference's loss terms, last logits, score and chosen experts
    per expert layer, the leaves' gradients and the leaves before and
    after their first AdamW step.  ``dtype=bfloat16`` is the reading
    below (module docstring); None is the reference proper.  The
    reference's by-block walk, and one small program for the update."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype or jnp.float32)
    with jax.default_matmul_precision("highest"):
        (total, ((main, aux), (logits, routing))), g = (
            ref.loss_and_grads_by_block(params, ids, labels, rcfg, dtype))
    rule = dict(clip=solver_cfg.clip_gradients, lr=first_lr(solver_cfg),
                beta1=solver_cfg.momentum, beta2=solver_cfg.momentum2,
                eps=solver_cfg.delta, weight_decay=solver_cfg.weight_decay)
    specs = tuple(which.items())
    before, after = jax.jit(_adamw_changes, static_argnums=(0, 3, 4, 5))(
        ref, params, g, tuple(rule.items()), specs, dtype.name)
    return {"total": total, "main": main.astype(jnp.float32),
            "aux": aux.astype(jnp.float32),
            "logits": logits[:, -LAST:].astype(jnp.float32),
            "scores": {n: s.astype(jnp.float32)
                       for n, (s, _) in routing.items()},
            "chosen": {n: c for n, (_, c) in routing.items()},
            "before": before, "after": after,
            "grad": {name: _leaf(g, spec, jnp) for name, spec in specs}}


def forward_program(solver):
    """One jitted program around the solver's own net: the loss terms, the
    last logits, every expert layer's routing (the layer's own ``route``
    on the layer's own input) and its counter.  Returns
    ``forward(variables, feeds)``."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.common import get_config, step_key
    from sparknet_tpu.ops.moe import route

    net = solver.train_net
    key = solver.jitted_train_step()[3]  # the solver's own root key
    cdt = get_config().compute_dtype
    layers = expert_layers(net)

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
                "load": {l.name: state[l.name]["load"] for l in layers}}

    go = jax.jit(go)
    return lambda variables, feeds: go(variables, feeds, key)


def run_program(solver, forward, ids, labels, which):
    """The same quantities as ``run_reference`` from the program: the
    forward's facts from ``forward``, then ONE step of the solver's own
    compiled step on the same sequence for the total loss and the
    leaves' change.  The solver is left one iteration on, as a run that
    began with this sequence would be."""
    import jax
    import jax.numpy as jnp

    feeds = {"data": jnp.asarray(ids), "label": jnp.asarray(labels)}
    got = jax.tree_util.tree_map(
        np.asarray, forward(solver.variables, feeds))
    leaves_now = lambda: {
        name: np.array(_leaf(solver.variables.params, spec, np))
        for name, spec in which.items()}
    got["before"] = leaves_now()
    got["total"] = solver.step(1, lambda it: feeds)
    got["after"] = leaves_now()
    return got


def compare(got: dict, want: dict, share: tuple[int, int] = (0, 0)) -> dict:
    """The facts (a) to (d) of ``got`` against the reference ``want``;
    ``share`` = (first expert held, experts held).  A run of the
    reference in another precision stands in for ``got`` too (it has no
    ``load``: the rows of the held experts are then not reported)."""
    facts = {}
    k = next(iter(want["chosen"].values())).shape[-1]
    for term in ("total", "main", "aux"):
        g, w = float(got[term]), float(want[term])
        facts[term], facts[term + "_ref"] = g, w
        facts[term + "_rel"] = abs(g - w) / abs(w)
    differ, gap, agree = 0, 0.0, None
    for name, w_ex in want["chosen"].items():
        g_ex = np.sort(np.asarray(got["chosen"][name]), -1)
        w_ex = np.sort(np.asarray(w_ex), -1)
        bad = np.any(g_ex != w_ex, axis=-1)
        agree = ~bad if agree is None else agree & ~bad
        differ += int(bad.sum())
        if bad.any():
            v = -np.sort(-np.asarray(want["scores"][name])[bad], axis=-1)
            gap = max(gap, float(((v[:, k - 1] - v[:, k]) / v[:, k - 1]).max()))
    facts["topk_sets_differ"], facts["tie_gap"] = differ, gap
    facts["tokens"] = int(agree.size)
    n, last = got["logits"].shape[:2]
    same = agree.reshape(n, -1)[:, -last:]
    facts["logits_rel_all"] = _rel(got["logits"], want["logits"])
    facts["logits_rel"] = _rel(got["logits"][same], want["logits"][same])
    facts["logits_positions"] = int(same.sum())
    change = lambda run, name: (
        np.asarray(run["after"][name]).astype(np.float32)
        - np.asarray(run["before"][name]).astype(np.float32))
    for name in got["after"]:
        size = np.abs(np.asarray(want["grad"][name], np.float32))
        moved, wanted = change(got, name), change(want, name)
        # of the entries that HAVE a gradient (an expert no pair reached
        # has none, and its lr * sign(noise) coins would be the median of
        # all): the largest tenth is what the limit is on
        some = size > 0
        masks = {"_all": np.ones(size.shape, bool)}
        for part, q in (("", 0.9), ("_half", 0.5)):
            masks[part] = (some & (size >= np.quantile(size[some], q))
                           if some.any() else some)
        for part, sure in masks.items():
            facts[f"update_rel{part}.{name}"] = _rel(moved[sure], wanted[sure])
        sure = masks["_all" if name in WHOLE else ""]
        facts[f"update_entries.{name}"] = int(sure.sum())
        facts[f"update_flipped.{name}"] = int(np.sum(
            np.sign(moved[sure]) != np.sign(wanted[sure])))
    if "load" in got:
        facts["load_max_over_mean"] = concentration(got["load"])
        first, held_n = share
        held = [np.asarray(l)[first:first + held_n]
                for l in got["load"].values()]
        facts["held_rows_min"] = int(min(h.min() for h in held))
        facts["held_pair_share"] = 100.0 * float(
            sum(h.sum() for h in held)
            / sum(np.sum(l) for l in got["load"].values()))
    return facts


def verdict(facts: dict, tol: dict) -> tuple[str, list[str]]:
    """(every reading beside its limit and the names of what failed, on
    one line; the problems as the job reports them)."""
    failed = [name for name, limit in tol.items()
              if not facts[name] <= limit]
    readings = " ".join(f"{name}={facts[name]:.3g}/{limit:g}"
                        for name, limit in tol.items())
    line = f"linear_check readings/limits: {readings}; failed: " + (
        ",".join(failed) or "none")
    return line, [f"{name} {facts[name]:.3g} > {tol[name]:g}"
                  for name in failed]


def check_step(solver, ref, config: dict, ids, labels, tol: dict, forward,
               want=None):
    """(facts, problems) of the program against the reference on the
    sequences ``ids`` / ``labels`` ([n, S] int32).  Steps the solver once
    (``run_program``).  ``forward``: the solver's ``forward_program``;
    ``want``: a reference run the caller already made from the solver's
    present state (``scratch/hybrid_readings.py --workload
    qwen3next-solo-s4096``)."""
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
