"""Architecture families of the benchmark, one module each.

A configuration file names its family by its ``"family"`` key, and
``load`` finds ``<family>.py`` in this directory.  Everything that belongs
to one architecture lives in that module; the code that drives cells, makes
weights, follows the reference and reads metrics reaches it only through
this interface:

    Arch.from_file(cfg)   the file's published keys; gives ``vocab_size``,
                          ``dtype`` and ``param_count(head)``, ``head``
                          being "lm" or "value"
    stated(arch)          the program ``ModelConfig`` fields and values that
                          the file fixes
    layout(arch, head)    the program's parameter tree as ShapeDtypeStructs
    fan_in(arch, path, shape)
                          the fan-in a matrix leaf is drawn at (its standard
                          deviation is fan_in**-0.5); ``path`` is the leaf's
                          ``jax.tree_util.keystr``
    forward(p, arch, tokens, dot)
                          final-norm hidden states (B, S, D) in float32,
                          every matrix product through ``dot``
    lm_head(p)            the (V, D) matrix the logits use
    calls(arch, batch, prompt, gen, minibatches)
                          FLOPs and bytes of each call of one PPO iteration

An architecture is added as a new module here, beside the configuration
files that name it; no file that is there changes.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
INTERFACE = ("Arch", "stated", "layout", "fan_in", "forward", "lm_head",
             "calls")


def known(directory: Path = HERE) -> list[str]:
    """The families that have a module in ``directory``."""
    return sorted(p.stem for p in Path(directory).glob("*.py")
                  if p.stem != "__init__")


def load(family: str | None, directory: Path = HERE) -> ModuleType:
    """The module of ``family`` in ``directory``, loaded once a process."""
    directory = Path(directory).resolve()
    families = known(directory)
    if family not in families:
        raise ValueError(f"architecture family {family!r} has no module in "
                         f"{directory}; the families there: {families}")
    name = (f"{__name__}.{family}" if directory == HERE
            else f"{__name__}.{family}@{directory}")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name,
                                                  directory / f"{family}.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
        missing = [k for k in INTERFACE if not hasattr(module, k)]
        if missing:
            raise ValueError(f"architecture family {family!r} lacks "
                             f"{missing} of the interface")
    except BaseException:
        del sys.modules[name]
        raise
    return module
