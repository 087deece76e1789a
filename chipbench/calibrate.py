#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,... \
        --faulted 3 --out <cell>.cal.jsonl

For each seed, in one process: the program's warm-up iterations through
the window's own call, then the float32 reference over the tokens they
served, and the numbers of ``check.numbers`` (the sound readings).  For the
first ``--faulted`` seeds also, each in the program's place against the
same float32 reference: the control (the reference with float8 matmuls),
the reference with each minibatch loss taken over half its rows, and the
program's rollout with one served token altered after its logprob was
taken.  One JSON line per reading.  The benchmark's own runs do not run
this.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path[:1] = [str(Path(__file__).resolve().parent.parent)]

import chipbench.run as R  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import check  # noqa: E402
from chipbench import weights as W  # noqa: E402
from chipbench.cell import load_cell  # noqa: E402
from chipbench.reference import Reference, _f32  # noqa: E402


def altered_token_gap(cell, wkey, prog, seed: int) -> float:
    """Iteration 0's rollout logprobs against the reference over the same
    sequences with one generated token changed (the policy is still the
    initial one there)."""
    it = prog["iterations"][0]
    seq = np.array(it["seq"])
    rng = np.random.default_rng(seed)
    row = int(rng.integers(seq.shape[0]))
    col = cell.prompt_len + int(rng.integers(cell.gen_len))
    seq[row, col] = (seq[row, col] + 1 + int(rng.integers(100))) % \
        cell.arch.vocab_size
    ref = Reference(cell.family, cell.arch, cell.hp, cell.prompt_len)
    with jax.default_matmul_precision("highest"):
        lm = _f32(W.make(wkey, cell.family, cell.arch)["lm"])
        lp = np.asarray(ref.logprobs(lm, jnp.asarray(seq)))
    return float(np.max(np.abs(it["logp"] - lp)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faulted", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devs, _ = R.chips_or_exit(cell.chips)
    R.enable_cache()
    compiles = R.CompileCounter()
    limit = min((d.memory_stats() or {}).get("bytes_limit", 0)
                for d in devs[:cell.chips])
    seeds = [int(s) for s in args.seeds.split(",")]
    programs = {}
    with open(args.out, "a") as f:
        def emit(kind, seed, nums):
            line = {"workload": cell.name, "kind": kind, "seed": seed, **nums}
            f.write(json.dumps(line) + "\n")
            f.flush()
            R.log(json.dumps(line))

        for k, seed in enumerate(seeds):
            run, prog, wkey, _, _ = R.warm_up(cell, seed, compiles,
                                              settle=False)
            R.free(run)
            del run
            seqs = [it["seq"] for it in prog["iterations"]]
            r32 = R.follow_reference(cell, wkey, seqs, limit,
                                     programs=programs)
            emit("sound", seed, check.numbers(prog, r32))
            if k >= args.faulted:
                continue
            for dot, fault in (("fp8", None), ("fp32", "half_batch")):
                other = R.follow_reference(cell, wkey, seqs, limit, dot=dot,
                                           fault=fault, programs=programs)
                emit(fault or "control_" + dot, seed,
                     check.numbers(check.as_program(other), r32))
            emit("altered_token", seed, {"rollout_logp": altered_token_gap(
                cell, wkey, prog, seed)})


if __name__ == "__main__":
    main()
