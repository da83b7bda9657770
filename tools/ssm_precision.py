"""What the ``ssmfit`` cell's comparison reads where it must fail: the
upper column of PERF.md's limits table for that cell.

    python3 tools/ssm_precision.py --workload <cell> --seeds 1,2,3
        [--scan-step-down] [--no-update] [--seconds 1] [--trace 0|1]

For each seed, one run of the cell through ``perfbench/run.py``'s
``main`` (the same server, traffic, reference and comparison), and then,
on that seed's seeded weights and batches:

- the control: the reference at ``precision.control`` against the
  reference steps the run just took at ``precision.reference`` (not
  taken again), through ``compare_tx.step_gaps``; and ``off.tx`` of the
  first ``ROWS`` test rows' class probabilities on the seeded weights,
  control against reference, at the cell's tolerance;
- with ``--no-update``, a fit whose state never changes: step 0 is the
  reference's own (the same weights and batch), and the losses of the
  later compared steps are the reference's forward pass on the seeded
  weights, each on its step's batch.

``--scan-step-down`` runs the program with the Mamba-2 scan's products
(``transformer._ssm_dot``) one precision step below the bfloat16 the
configuration states: every operand rounded to ``float8_e4m3fn``, then
the stated default-precision product.

Standard output: the run's own lines, then one JSON line a seed,
``{"seed", "correct", "failing", "run", "control", "no_update",
"seconds"}``, where ``run`` and ``control`` hold every number read and
``failing`` names the run's checks over their limits.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import io
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import cells, compare_tx, run  # noqa: E402
from perfbench.traffic import txfit  # noqa: E402

#: Test rows the control's ``off.tx`` reads: four a seed, as the other
#: ``tx`` cells' controls read them (each a forward pass of the reference
#: at two precisions).
ROWS = 4


def scan_step_down() -> None:
    """The program's scan products on float8 operands from here on."""
    import jax.numpy as jnp

    from learningorchestra_tpu.models import sequence
    from learningorchestra_tpu.models import transformer as tx

    def down(t):
        return t.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    def fp8_dot(spec, a, b):
        return jnp.einsum(spec, down(a), down(b), precision=tx._SSM_PRECISION)

    tx._ssm_dot = fp8_dot
    sequence._fit_programs.cache_clear()
    sequence._proba_program.cache_clear()


def _batches(R, conf, tr, seed):
    hp = conf["families"]["tx"]
    s = int(seed) % (2 ** 31 - 1)
    train, y, test, _ = txfit.make_tables(conf, int(seed))
    return s, test, [(train[r], y[r]) for r in (
        R.batch_rows(s, k, hp["batch"], len(train))
        for k in range(int(tr["steps_compared"])))]


def no_update_steps(R, conf, w, batches, ref_steps) -> list:
    """The step reports of a fit whose weights never move: step 0 the
    reference's; the later steps' losses the seeded weights' own."""
    import jax
    import jax.numpy as jnp

    z, prec = R.sizes(conf), conf["precision"]["reference"]
    loss = jax.jit(lambda w, t, lab: R.loss_parts(w, t, lab, z, prec))
    out = [ref_steps[0]]
    with jax.default_matmul_precision("highest"):
        flat = {p: jnp.asarray(a, jnp.float32)
                for p, a in R.unstack(w, z).items()}
        for tokens, labels in batches[1:]:
            lm, li = loss(flat, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(labels, jnp.int32))
            out.append({"loss_main": float(lm), "loss_index": float(li),
                        "grad_norm": ref_steps[0]["grad_norm"]})
    return out


def probe(cell: dict, seed: int, argv: list, root: str, no_update: bool,
          device=None) -> dict:
    tr, conf = cell["traffic"], cell["config"]
    R = importlib.import_module("perfbench." + tr["reference"])
    # The run's reference steps, as the run hands them to its comparison.
    # Nothing here may hold the seeded weights while the reference takes
    # its steps: they and the steps' state fill the chip.
    taken = []
    real = compare_tx.compare

    def kept(fits, unfinished, ref_steps, *a, **kw):
        taken.append(ref_steps)
        return real(fits, unfinished, ref_steps, *a, **kw)

    t0 = time.time()
    out = io.StringIO()
    compare_tx.compare = kept
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(argv, root=root, device=device)
    finally:
        compare_tx.compare = real
        sys.stdout.write(out.getvalue())
        sys.stdout.flush()
    if rc != 0 or not taken:
        return {"seed": seed, "rc": rc}
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    ref = taken[-1]
    t1 = time.time()
    s, test, batches = _batches(R, conf, tr, seed)
    ctl = R.adam_steps(conf, R.init_weights(conf, s), batches,
                       conf["families"]["tx"]["lr"],
                       conf["precision"]["control"])
    reads = compare_tx.step_gaps(ctl, ref)
    w = R.init_weights(conf, s)
    n = conf["data"]["num_classes"]
    p_ref = R.class_probs(conf, w, test[:ROWS], n,
                          conf["precision"]["reference"])
    p_ctl = R.class_probs(conf, w, test[:ROWS], n,
                          conf["precision"]["control"])
    gaps = np.abs(p_ctl - p_ref).max(-1)
    reads["off.tx"] = float(np.mean(gaps > cell["tolerance"]["tx"]))
    reads["row_gaps"] = [float(g) for g in gaps]
    t2 = time.time()
    result = {"seed": seed, "correct": last["correct"],
              "failing": sorted(k for k, c in last["checks"].items()
                                if not c["value"] <= c["limit"]),
              "run": last.get("observed", {}), "control": reads}
    if no_update:
        result["no_update"] = compare_tx.step_gaps(
            no_update_steps(R, conf, w, batches, ref), ref)
    result["seconds"] = {"run": round(t1 - t0, 1),
                         "control": round(t2 - t1, 1),
                         "no_update": round(time.time() - t2, 1)}
    return result


def main(argv=None, root: str = ROOT, device=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scan-step-down", action="store_true")
    ap.add_argument("--no-update", action="store_true",
                    help="read the no-update fault on the first seed")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload, root)
    if args.scan_step_down:
        scan_step_down()
    seeds = [int(s) for s in args.seeds.split(",")]
    worst = 0
    for i, seed in enumerate(seeds):
        result = probe(cell, seed, [
            "--workload", args.workload, "--seed", str(seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace)],
            root, args.no_update and i == 0, device)
        print(json.dumps(result), flush=True)
        worst = max(worst, result.get("rc", 0))
    return worst


if __name__ == "__main__":
    sys.exit(main())
