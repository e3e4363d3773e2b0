"""chainermn_tpu — TPU-native distributed training framework.

A ground-up JAX/XLA/Pallas rebuild of the capability surface of
levelfour/chainermn (see SURVEY.md): communicator backends lowering to XLA
collectives over ICI/DCN, a multi-node optimizer wrapper, dataset
scattering, synchronized/multi-node iterators, a multi-node evaluator,
synchronized batch normalization, differentiable point-to-point and
collective communication, a MultiNodeChainList-style model-parallel API,
ring-attention / Ulysses sequence parallelism, and distributed
checkpoint/resume.

Facade parity: ``chainermn/__init__.py`` re-exports (component #1 in
SURVEY.md section 2).
"""

import time as _time

_t_first_line = _time.monotonic()  # where setup.before_program ends

from chainermn_tpu.observability import timeline as _timeline  # noqa: E402

_import_phase = _timeline.PROCESS.program_starts(_t_first_line)

from chainermn_tpu.communicators import (  # noqa: F401,E402
    CommunicatorBase,
    create_communicator,
)
from chainermn_tpu.optimizers import (  # noqa: F401
    create_multi_node_optimizer,
    build_train_step,
)
from chainermn_tpu.datasets import (  # noqa: F401
    scatter_dataset,
    create_empty_dataset,
)
from chainermn_tpu.extensions import (  # noqa: F401
    create_multi_node_evaluator,
    create_multi_node_checkpointer,
    AllreducePersistent,
)
from chainermn_tpu import global_except_hook  # noqa: F401

__version__ = "0.2.0"

_import_phase.__exit__(None, None, None)  # setup.import ends here
_timeline.PROCESS.listen()  # JAX's trace / lower / compile events
del _import_phase, _t_first_line


def __getattr__(name):
    # Heavier subsystems load lazily to keep import light.
    if name in ("functions", "links", "iterators", "training", "parallel",
                "models", "ops", "utils", "resilience", "comm_wire",
                "observability", "serving", "fleet"):
        import importlib

        return importlib.import_module(f"chainermn_tpu.{name}")
    if name == "MultiNodeChainList":
        from chainermn_tpu.link import MultiNodeChainList

        return MultiNodeChainList
    if name == "create_multi_node_iterator":
        from chainermn_tpu.iterators import create_multi_node_iterator

        return create_multi_node_iterator
    if name == "create_synchronized_iterator":
        from chainermn_tpu.iterators import create_synchronized_iterator

        return create_synchronized_iterator
    if name == "prefetch_to_device":
        from chainermn_tpu.iterators import prefetch_to_device

        return prefetch_to_device
    raise AttributeError(name)
