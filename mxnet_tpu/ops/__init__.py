"""Operator registry and op families (imported for registration side effects)."""
from . import registry  # noqa: F401
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import random_ops  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import rnn_op  # noqa: F401
from . import attention  # noqa: F401
from . import ssm  # noqa: F401
from . import gdn  # noqa: F401
from . import latent  # noqa: F401
from . import sparse_latent  # noqa: F401
from . import spatial  # noqa: F401
from . import optim_ops  # noqa: F401
from . import sharded_ops  # noqa: F401
from .registry import OP_REGISTRY, Op, get_op, list_ops, register  # noqa: F401
