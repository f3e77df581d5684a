"""Algorithm flavors.

Reference parity: ``core/.../controller/P2LAlgorithm.scala`` (distributed
train -> local model), ``PAlgorithm.scala`` (distributed model),
``LAlgorithm.scala`` (local train/model), ``PersistentModel.scala`` /
``LocalFileSystemPersistentModel.scala``.

TPU re-design: the P2L/P split existed because Spark models either fit the
driver or stay as RDDs. On TPU both collapse into ``JaxAlgorithm`` — train
runs under jit on mesh-sharded arrays; the model is a pytree that may be
sharded across HBM during training but is always checkpointed
sharding-agnostically (host numpy) and re-laid-out at deploy. ``LocalAlgorithm``
covers host-only (pure Python/NumPy) algorithms, the analog of LAlgorithm.
"""

from __future__ import annotations

import importlib
import os
import uuid
from typing import Any, Generic

import jax
import numpy as np

from predictionio_tpu.controller.base import M, PD, Q, P, BaseAlgorithm
from predictionio_tpu.workflow.context import WorkflowContext


def model_to_host(model: Any) -> Any:
    """Pull every jax array in a model pytree to host numpy — the
    sharding-agnostic checkpoint form (SURVEY.md hard part (f): train on a
    v5e-16, serve on one host).

    Arrays sharded across *processes* are not fully addressable from any one
    host; those are gathered with a cross-host collective first (every
    process must call this — it happens inside make_serializable_models,
    which all processes run)."""

    def pull(x):
        if not isinstance(x, jax.Array):
            return x
        if not x.is_fully_addressable:
            from jax.experimental import multihost_utils

            x = multihost_utils.process_allgather(x, tiled=True)
        return np.asarray(jax.device_get(x))

    return jax.tree_util.tree_map(pull, model)


class JaxAlgorithm(BaseAlgorithm[PD, M, Q, P], Generic[PD, M, Q, P]):
    """An algorithm whose train() builds a jax pytree model on the context's
    mesh and whose predict path is a compiled function.

    Subclasses implement ``train`` and ``predict``; ``batch_predict`` may be
    overridden with a vectorized (vmap/jit) implementation — the default maps
    ``predict``.
    """

    def make_persistent_model(self, ctx: WorkflowContext, model: M) -> Any:
        """A :class:`PersistentModel` keeps its own storage: it is saved
        under a fresh id and only its manifest goes into the model
        repository's blob. Every other model is pulled to the host."""
        if isinstance(model, PersistentModel):
            model_id = uuid.uuid4().hex
            if model.save(model_id, self.params, persistent_model_dir()):
                cls = type(model)
                return PersistentModelManifest(f"{cls.__module__}.{cls.__qualname__}", model_id)
        return model_to_host(model)

    def prepare_model(self, ctx: WorkflowContext, persisted: Any) -> M:
        """Default re-layout: leave arrays on host; algorithms that want
        device-resident serving override and device_put with their preferred
        shardings. A manifest is resolved to the model its class loads."""
        if isinstance(persisted, PersistentModelManifest):
            module, _, name = persisted.class_path.rpartition(".")
            cls = getattr(importlib.import_module(module), name)
            return cls.load(persisted.model_id, self.params, persistent_model_dir())
        return persisted


class LocalAlgorithm(BaseAlgorithm[PD, M, Q, P], Generic[PD, M, Q, P]):
    """Host-only algorithm (ref LAlgorithm): pure Python/NumPy train and
    predict, no device interaction. Participates in batch eval by plain
    mapping."""


class PersistentModel:
    """Models managing their own storage (ref PersistentModel.scala:115).

    A model class implementing ``save``/``load`` is persisted by calling
    ``save`` and recording a manifest; at deploy, ``load`` rebuilds it.
    """

    def save(self, instance_id: str, params: Any, base_dir: str) -> bool:
        """Persist; return False to fall back to default pytree persistence."""
        raise NotImplementedError

    @classmethod
    def load(cls, instance_id: str, params: Any, base_dir: str) -> "PersistentModel":
        raise NotImplementedError


class PersistentModelManifest:
    """Marker stored in the model repo instead of bytes
    (ref workflow/PersistentModelManifest.scala): the class that loads the
    model and the id it was saved under."""

    def __init__(self, class_path: str, model_id: str = ""):
        self.class_path = class_path
        self.model_id = model_id

    def to_json_dict(self) -> dict[str, str]:
        return {"class_path": self.class_path, "model_id": self.model_id}


def persistent_model_dir() -> str:
    """Where :class:`PersistentModel`s keep their files:
    ``$PIO_FS_BASEDIR/models`` (``~/.pio_store/models`` by default), one
    directory a saved model."""
    base = os.environ.get("PIO_FS_BASEDIR", os.path.join(os.path.expanduser("~"), ".pio_store"))
    return os.path.join(base, "models")
