"""On-chip benchmark of the RLHF main path (see ``run.py``).

Everything that belongs to one model configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    configs/<config>.json   sizes as run, with the published source; its
                            ``family`` names the architecture's module
    archs/<family>.py       one architecture: its keys, parameter layout,
                            reference forward pass and FLOP counts
    traffic/<mix>.json      batch, lengths and PPO knobs of one job
    metrics/<metric>.py     one reader per per-layer metric
    limits/<cell>.json      the limits of the correctness comparison
"""
