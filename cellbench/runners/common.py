"""What both training runners share: loading an example by path, seeds,
reading the optimizer's first gradient out of its state, and the first
steps that ``correct`` is decided from."""

from __future__ import annotations

import importlib
import importlib.util
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_example(rel_path: str):
    """Import ``examples/<rel_path>`` the way ``python examples/...``
    finds it (the examples are scripts, not a package)."""
    path = os.path.join(ROOT, "examples", rel_path)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_reference(config: dict):
    """The plain reference the configuration names:
    ``reference/<config["reference"]>.py``."""
    return importlib.import_module(
        "cellbench.reference." + config["reference"])


def reuse_large_host_buffers():
    """Have glibc's malloc serve large requests from its heap and keep
    what is freed there, so that a batch buffer of tens of MB is the
    same touched pages step after step.  By default each is a fresh
    ``mmap`` whose every page faults on first touch, and what a fault
    costs depends on how much of its memory the machine has touched
    before (a young virtual machine: several times more).  The
    environment's ``MALLOC_ARENA_MAX=1 MALLOC_MMAP_MAX_=0
    MALLOC_TRIM_THRESHOLD_=2147483647`` does the same for a user's job."""
    import ctypes

    libc = ctypes.CDLL("libc.so.6")
    m_trim_threshold, m_mmap_max, m_arena_max = -1, -4, -8  # <malloc.h>
    # one arena: a thread's own arena cannot hold such a buffer and
    # falls back to mmap whatever M_MMAP_MAX says
    if not (libc.mallopt(m_arena_max, 1) and libc.mallopt(m_mmap_max, 0)
            and libc.mallopt(m_trim_threshold, 2**31 - 1)):
        raise RuntimeError("mallopt refused the allocator settings")


def batch_rng(seed: int, index: int) -> np.random.Generator:
    """The generator of batch ``index`` under ``--seed``."""
    return np.random.default_rng([int(seed), int(index)])


def find_state(opt_state, field: str):
    """The first node of an optax state tree that has ``field`` (``mu``
    of Adam, ``trace`` of momentum SGD)."""
    import jax

    has = lambda x: hasattr(x, field) and hasattr(x, "_fields")
    for node in jax.tree_util.tree_leaves(opt_state, is_leaf=has):
        if has(node):
            return getattr(node, field)
    raise LookupError(f"no optimizer state with a {field!r} field")


class TrainCell:
    """A training cell as the harness drives it.  A runner subclasses it
    and provides ``dispatch``, ``reseed``, ``_first_gradient``,
    ``_delta_norms``, ``first_inputs``, ``reference`` and ``free``."""

    #: samples (tokens, images) one step consumes, all chips together
    samples_per_step: int = 0
    #: steps the reference follows
    first_n = 3

    def first_steps(self) -> dict:
        """Drive the first steps through the window's own call and feed,
        reading what ``correct`` compares: every step's loss, the first
        gradient as the optimizer got it, the parameters' change."""
        losses, grad_norms, grad_small = [], None, None
        for i in range(self.first_n):
            losses.append(float(self.dispatch()))
            if i == 0:
                grad_norms, grad_small = self._first_gradient()
        return {"losses": losses, "grad_norms": grad_norms,
                "grad_small": grad_small,
                "delta_norms": self._delta_norms()}

    def start_window(self):
        """Called once, right before the timed window."""

    def telemetry(self):
        """The program's own spans of the window, or None."""
        return None
