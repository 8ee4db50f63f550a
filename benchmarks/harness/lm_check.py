"""The comparison that decides ``correct`` in the language-model cells.

Outside the timed window, on ONE seeded sequence at the configuration's
own widths, the program (bf16 matmuls; f32 parameters, router softmax,
RMSNorm statistics, cross-entropy and AdamW state) is held to the plain
reference (``benchmarks/reference/olmoe.py``: f32 at ``highest`` matmul
precision, from the solver's own initial parameters):

(a) the three loss terms separately (cross-entropy, load-balancing,
    router z-loss, each as the prototxt's top reports it) and their
    weighted total;
(b) the logits of the last 256 positions against the reference's full
    forward (rel-L2), over the positions whose tokens were routed as the
    reference routed them in every layer (a token that a near-tie sent to
    another expert has other logits, and (c) is what holds it; the value
    over all 256 is reported as ``logits_rel_all``);
(c) routing: the number of tokens whose top-k expert SET differs from the
    reference's is reported, and each such token must be a near-tie in
    the reference: (p_k - p_{k+1}) / p_k no larger than the limit;
(d) the first AdamW step's change of two leaves (the router weight, the
    final RMSNorm weight) against the reference's gradients put through
    the reference's clip and AdamW rule (rel-L2 of the change).

Each limit is set from two readings, both on the chip (my chip runs,
PR 26; PERF.md section 6): the largest value the program gave over 13
seeds, and what the reference itself gives when EVERYTHING is computed
in bf16, router softmax, RMSNorm statistics and cross-entropy included
(``run_reference(dtype=bfloat16)``: the nearest precision below the
configuration's; 3 seeds), which has to come out as not correct.  It does,
on every seed, by the cross-entropy: a bf16 log-softmax over 12,576 rows
reads 9.8e-4 to 3.1e-3 where the program reads at most 1.5e-4.

* cross-entropy, |rel| <= 5e-4 (program 8e-6 to 1.5e-4; all-bf16 9.8e-4,
  2.7e-3, 3.1e-3).  Weighted total <= 5e-4 (program <= 1.6e-4; all-bf16
  4.6e-4, 3.5e-3, 4.8e-3).
* router z-loss, |rel| <= 1.2e-3 (program 1.2e-5 to 3.9e-4; all-bf16
  3.1e-4, 1.9e-3, 2.5e-3: a bf16 logsumexp fails it on two seeds of
  three).  A z-loss left out reads 1.0 on this term.
* load-balancing loss, |rel| <= 6e-3 (program 9e-5 to 1.8e-3; all-bf16
  3e-6 to 2.5e-3).  The term counts tokens per expert, so its error is
  the near-tie tokens of (c) and no precision moves it much; a
  renormalised top-k leaves it alone too and shows in the logits.
* logits, rel-L2 over the routing-agreeing positions of the last 256:
  <= 1.2e-2.  The program reads 6.4e-3 to 7.2e-3 on every seed (bf16
  keeps 8 mantissa bits through the block's matmuls), the all-bf16
  reference 8.0e-3 to 8.7e-3: this limit does NOT separate the two, the
  cross-entropy does.  Over all 256 positions the program reads 1.2e-2
  to 2.6e-2: 104 to 266 of the 4,096 tokens are routed differently at a
  near-tie, each moving its own logits by ~10 %.
* near-tie limit (c), (p_k - p_{k+1}) / p_k <= 6e-2.  A router logit is
  a 2048-term bf16 dot product of a normalized row; two of them differ
  from their f32 values by up to ~3e-2.  Program 1.4e-2 to 2.7e-2;
  all-bf16 2.1e-2 to 2.9e-2.
* update of the router weight, rel-L2 of the change: <= 0.4 (program
  0.093 to 0.157; all-bf16 0.096 to 0.131).  The first Adam step is
  lr * sign(g) wherever |g| >> eps, so every entry whose gradient's sign
  bf16 noise flips changes by 2 lr: a share f of flipped signs reads
  2 sqrt(f), 0.157 is 0.6 % of the entries.  In f32 on the CPU the same
  comparison gives <= 1e-4 on the entries clear of zero
  (tests/test_olmoe.py).  Decay put into the gradient (Caffe's Adam
  form: 0.1 * w beside a clipped gradient) would decide most signs by
  the weight's and read ~1.4, a gradient of the wrong sign 2.0; neither
  was run on the chip.
* update of the final RMSNorm weight: <= 0.3 (program 0.071 to 0.118;
  all-bf16 0.091 to 0.103).  L2-style decay would add +0.1 to every
  entry's gradient (the weights are 1), far above the clipped gradient,
  and read ~1.
"""

from __future__ import annotations

import numpy as np

TOL = {"ce_rel": 5e-4, "lb_rel": 6e-3, "z_rel": 1.2e-3, "total_rel": 5e-4,
       "logits_rel": 1.2e-2, "tie_gap": 6e-2,
       "update_rel.router": 0.4, "update_rel.norm_f": 0.3}
# a CPU rehearsal runs a tiny-width preset on 32 tokens: bf16 noise does
# not average out over so few.  It walks the code; the chip run at the
# published widths is what holds the program.
TOL_REHEARSE = {"ce_rel": 2e-2, "lb_rel": 2e-2, "z_rel": 2e-2,
                "total_rel": 2e-2, "logits_rel": 1e-1, "tie_gap": 1.0,
                "update_rel.router": 2.0, "update_rel.norm_f": 2.0}
LAST = 256  # positions whose logits are compared


def tolerances(rehearse: bool = False) -> dict:
    return dict(TOL_REHEARSE if rehearse else TOL)


def reference_config(config: dict) -> dict:
    """The sizes and weights ``reference/olmoe.py`` takes, from a
    configuration file."""
    return {"heads": config["num_attention_heads"],
            "top_k": config["num_experts_per_tok"],
            "layers": config["num_hidden_layers"],
            "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"]),
            "lb_weight": config["load_balancing_weight"],
            "z_weight": config["router_z_loss_weight"]}


def leaves(config: dict) -> dict:
    """name -> (layer, blob): the last layer's router weight, the final
    RMSNorm weight."""
    return {"router": (f"moe{config['num_hidden_layers']}", 0),
            "norm_f": ("norm_f", 0)}


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _adamw_changes(ref, params, grads, solver_cfg, which: dict):
    """The first AdamW step's change of the leaves ``which``, from ALL
    the gradients (the clip is global)."""
    scale = ref.clip_scale(grads, solver_cfg.clip_gradients)
    out = {}
    for name, (layer, i) in which.items():
        w0 = params[layer][i]
        w1, _, _ = ref.adamw_step(
            w0, grads[layer][i] * scale, 0.0, 0.0, 1, lr=solver_cfg.base_lr,
            beta1=solver_cfg.momentum, beta2=solver_cfg.momentum2,
            eps=solver_cfg.delta, weight_decay=solver_cfg.weight_decay)
        out[name] = w1 - w0
    return out


def run_reference(ref, params, ids, labels, rcfg, solver_cfg, which,
                  dtype=None):
    """One jitted program: the reference's loss terms, last logits, router
    probabilities and chosen experts per layer, and the leaves' first
    AdamW change.  ``dtype=bfloat16`` is the reading below (module
    docstring); None is the reference proper."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32

    def go(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            (total, ((ce, lb, z), (logits, router_logits, chosen))), g = \
                jax.value_and_grad(ref.loss, has_aux=True)(
                    params, ids, labels, rcfg, dtype)
        g = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), g)
        return {"total": total, "ce": ce, "lb": lb, "z": z,
                "logits": logits[:, -LAST:].astype(jnp.float32),
                "probs": [jax.nn.softmax(rl.astype(jnp.float32), axis=-1)
                          for rl in router_logits],
                "chosen": chosen,
                "change": _adamw_changes(ref, params, g, solver_cfg, which)}

    return jax.jit(go)(params, ids, labels)


def run_program(solver, config: dict, ids, labels, which):
    """One jitted program around the solver's own step and net: the same
    quantities as ``run_reference`` from the program."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.common import get_config, step_key
    from sparknet_tpu.ops.moe import route

    net = solver.train_net
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    n_layers, k = config["num_hidden_layers"], config["num_experts_per_tok"]
    cdt = get_config().compute_dtype

    def go(variables, slots, feeds, key):
        blobs, _, total = net.apply(variables, feeds, rng=step_key(key, 0))
        stepped, _, _ = fn(variables, slots, 0, feeds, key)
        probs, chosen = [], []
        for i in range(1, n_layers + 1):
            # the layer's own routing on the layer's own input
            x = blobs[f"norm{i}b"]
            _, p, _, experts = route(
                variables.params[f"moe{i}"][0].astype(cdt),
                x.reshape(-1, x.shape[-1]), k)
            probs.append(p)
            chosen.append(experts)
        mean = lambda tops: sum(blobs[t] for t in tops) / n_layers
        return {"total": total, "ce": blobs["loss"],
                "lb": mean([f"lb{i}" for i in range(1, n_layers + 1)]),
                "z": mean([f"z{i}" for i in range(1, n_layers + 1)]),
                "logits": blobs["lm_head"][:, -LAST:].astype(jnp.float32),
                "probs": probs, "chosen": chosen,
                "load": [blobs[f"load{i}"] for i in range(1, n_layers + 1)],
                "change": {name: stepped.params[l][i] - variables.params[l][i]
                           for name, (l, i) in which.items()}}

    feeds = {"data": jnp.asarray(ids), "label": jnp.asarray(labels)}
    return jax.jit(go)(variables, slots, feeds, key)


def compare(got: dict, want: dict, k: int) -> dict:
    """The facts (a) to (d) of ``got`` against the reference ``want``."""
    facts = {}
    for term in ("total", "ce", "lb", "z"):
        g, w = float(got[term]), float(want[term])
        facts[term], facts[term + "_ref"] = g, w
        facts[term + "_rel"] = abs(g - w) / abs(w)
    differ, gap = 0, 0.0
    agree = np.ones(np.asarray(want["chosen"][0]).shape[0], bool)
    for g_ex, w_ex, w_p in zip(got["chosen"], want["chosen"], want["probs"]):
        g_ex, w_ex = np.sort(np.asarray(g_ex), -1), np.sort(np.asarray(w_ex), -1)
        bad = np.any(g_ex != w_ex, axis=-1)
        agree &= ~bad
        differ += int(bad.sum())
        if bad.any():
            p = -np.sort(-np.asarray(w_p)[bad], axis=-1)  # descending
            gap = max(gap, float(((p[:, k - 1] - p[:, k]) / p[:, k - 1]).max()))
    facts["topk_sets_differ"], facts["tie_gap"] = differ, gap
    facts["tokens"] = int(agree.size)
    # (b): a token routed to another expert at a near-tie has other logits
    # for a reason (c) already holds; compare where the routing agrees
    n, last = got["logits"].shape[:2]
    same = agree.reshape(n, -1)[:, -last:]
    facts["logits_rel_all"] = _rel(got["logits"], want["logits"])
    facts["logits_rel"] = _rel(got["logits"][same], want["logits"][same])
    facts["logits_positions"] = int(same.sum())
    for name in got["change"]:
        facts[f"update_rel.{name}"] = _rel(got["change"][name],
                                           want["change"][name])
    return facts


def check_step(solver, ref, config: dict, ids, labels, tol: dict):
    """(facts, problems) of the program against the reference on the
    sequences ``ids`` / ``labels`` ([n, S] int32)."""
    import jax
    import jax.numpy as jnp

    which = leaves(config)
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), solver.variables.params)
    want = run_reference(ref, params, jnp.asarray(ids), jnp.asarray(labels),
                         reference_config(config), solver.config, which)
    want = jax.tree_util.tree_map(np.asarray, want)
    got = jax.tree_util.tree_map(
        np.asarray, run_program(solver, config, ids, labels, which))
    facts = compare(got, want, config["num_experts_per_tok"])
    # the program's own counter against the reference's chosen sets
    facts["load_matches_own_routing"] = all(
        np.array_equal(np.asarray(load),
                       np.bincount(np.asarray(ex).reshape(-1),
                                   minlength=config["num_experts"]))
        for load, ex in zip(got["load"], got["chosen"]))
    problems = [f"{name} {facts[name]:.3g} > {limit:g}"
                for name, limit in tol.items() if not facts[name] <= limit]
    if not facts["load_matches_own_routing"]:
        problems.append("tokens-per-expert counter differs from the routing")
    return facts, problems
