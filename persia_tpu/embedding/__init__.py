"""Embedding subsystem: hashing/routing, parameter store, worker tier,
sparse optimizers (ref: persia/embedding/ + rust/persia-embedding-server)."""

from persia_tpu.config import HyperParameters as EmbeddingHyperParameters  # noqa: F401
from persia_tpu.embedding.optim import SGD, Adagrad, Adam  # noqa: F401
from persia_tpu.embedding.store import EmbeddingStore  # noqa: F401
from persia_tpu.embedding.worker import EmbeddingWorker  # noqa: F401
