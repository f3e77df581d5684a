"""DASE base classes and the component-instantiation Doer.

Reference parity: ``core/.../core/BaseDataSource.scala``,
``BasePreparator.scala``, ``BaseAlgorithm.scala``, ``BaseServing.scala``,
``AbstractDoer.scala`` (reflective ctor(Params) instantiation),
``controller/SanityCheck.scala``.

Type parameters follow the reference's ``Engine[TD, EI, PD, Q, P, A]``:
  TD = training data, EI = evaluation info, PD = prepared data,
  Q = query, P = predicted result, A = actual result.

The reference's L/P duality (local objects vs RDDs) collapses here: training
data is whatever the DataSource returns (typically a ``ColumnarEvents`` block
or jax arrays); distribution is expressed by sharding inside the algorithm,
not by the type system.
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, Generic, Sequence, TypeVar

from predictionio_tpu.controller.params import EmptyParams, Params
from predictionio_tpu.workflow.context import WorkflowContext

TD = TypeVar("TD")
EI = TypeVar("EI")
PD = TypeVar("PD")
Q = TypeVar("Q")
P = TypeVar("P")
A = TypeVar("A")
M = TypeVar("M")  # model


class SanityCheck:
    """Optional mixin for TD/PD/model types: ``sanity_check`` is invoked
    after each stage unless --skip-sanity-check (ref Engine.scala:650-706)."""

    def sanity_check(self) -> None:
        raise NotImplementedError


class Doer:
    """Instantiate a DASE component class with its Params
    (ref AbstractDoer.scala:69 — ctor(params) with fallback to no-arg)."""

    @staticmethod
    def apply(cls: type, params: Params | None = None) -> Any:
        params = params if params is not None else EmptyParams()
        try:
            sig = inspect.signature(cls.__init__)
            takes_params = len(sig.parameters) > 1  # beyond self
        except (TypeError, ValueError):
            takes_params = False
        if takes_params:
            return cls(params)
        return cls()


class BaseDataSource(Generic[TD, EI, Q, A]):
    """Reads training and evaluation data (ref BaseDataSource.scala:55)."""

    params: Params

    def __init__(self, params: Params | None = None):
        self.params = params if params is not None else EmptyParams()

    def read_training(self, ctx: WorkflowContext) -> TD:
        raise NotImplementedError

    def read_eval(self, ctx: WorkflowContext) -> Sequence[tuple[TD, EI, Sequence[tuple[Q, A]]]]:
        """k folds of (trainingData, evalInfo, [(query, actual)])."""
        raise NotImplementedError


class BasePreparator(Generic[TD, PD]):
    """ref BasePreparator.scala:45."""

    params: Params

    def __init__(self, params: Params | None = None):
        self.params = params if params is not None else EmptyParams()

    def prepare(self, ctx: WorkflowContext, training_data: TD) -> PD:
        raise NotImplementedError


class IdentityPreparator(BasePreparator[TD, TD]):
    """Pass-through preparator (ref IdentityPreparator.scala:91)."""

    def prepare(self, ctx: WorkflowContext, training_data: TD) -> TD:
        return training_data


class BaseAlgorithm(Generic[PD, M, Q, P]):
    """ref BaseAlgorithm.scala:58-126. Subclasses are the three flavors in
    ``controller/algorithm.py``; this class defines the train/predict
    contract plus model-persistence hooks."""

    params: Params

    def __init__(self, params: Params | None = None):
        self.params = params if params is not None else EmptyParams()

    def train(self, ctx: WorkflowContext, prepared_data: PD) -> Any:
        raise NotImplementedError

    def predict(self, model: Any, query: Q) -> P:
        raise NotImplementedError

    def batch_predict(self, model: Any, queries: Sequence[tuple[int, Q]]) -> list[tuple[int, P]]:
        """Default: map predict over indexed queries (ref P2LAlgorithm
        default batchPredict :69-71). Jax algorithms override with a
        vectorized path."""
        return [(i, self.predict(model, q)) for i, q in queries]

    def predict_batch(self, model: Any, queries: Sequence[Q]) -> list[P]:
        """Serving-side micro-batch hook: predict a batch of *live* queries
        in one device call. The query server's dispatcher coalesces
        concurrent /queries.json requests into one call here — the TPU answer
        to the reference's per-request actor dispatch (and its literal
        ``TODO: Parallelize``, CreateServer.scala:488-491). Default maps
        ``predict``; device-backed algorithms override with one batched
        kernel so N concurrent requests cost one device round-trip."""
        return [self.predict(model, q) for q in queries]

    def predict_batch_dispatch(
        self, model: Any, queries: Sequence[Q]
    ) -> Callable[[], list[P]] | None:
        """Pipelined serving hook: *dispatch* the batch's device work without
        blocking and return a zero-arg finalize callable that fetches and
        decodes the results. The query server dispatches batch n+1 while
        batch n's results are still crossing the transport, so sustained
        throughput approaches the pure device-batched rate and per-request
        latency approaches one transport round-trip. Return None (the
        default) to use the synchronous ``predict_batch`` path."""
        return None

    def warmup_serving(self, model: Any, max_batch: int) -> None:
        """Deploy-time warm-up: pre-compile the device programs the serving
        path will hit (e.g. every power-of-two batch bucket up to
        ``max_batch``) so the first burst of traffic doesn't pay XLA
        compiles. Called by the query server at start and after /reload.
        Default: nothing to warm."""

    def batch_limit(self) -> int | None:
        """The most queries ONE dispatched batch should bring this
        algorithm, where its programs have a size of their own (a group of
        device state that holds so many sessions: one query more costs a
        second group's whole work). The query server closes its batches at
        the least of its ``max_batch_size`` and its algorithms' limits.
        Default: none, the server's own."""
        return None

    def register_metrics(self, registry: Any) -> None:
        """Declare this algorithm's own instruments in ``registry`` (an
        ``obs.metrics.MetricsRegistry``): the query server calls it with its
        registry for every algorithm it is about to serve, at start and
        after /reload, so an engine's counters are the engine's and the
        server knows none of them. Default: the algorithm has none."""

    # -- persistence hooks (ref makePersistentModel, BaseAlgorithm.scala:95)
    def make_persistent_model(self, ctx: WorkflowContext, model: Any) -> Any:
        """Return the object to persist for this model. Default: the model
        itself (everything here is a picklable pytree; the reference's
        'unit sentinel, retrain on deploy' mode is intentionally dropped —
        see SURVEY.md section 7 hard part (c))."""
        return model

    def prepare_model(self, ctx: WorkflowContext, persisted: Any) -> Any:
        """Rehydrate the persisted object at deploy time (inverse of
        make_persistent_model)."""
        return persisted


class BaseServing(Generic[Q, P]):
    """ref BaseServing.scala:54."""

    params: Params

    def __init__(self, params: Params | None = None):
        self.params = params if params is not None else EmptyParams()

    def supplement(self, query: Q) -> Q:
        return query

    def serve(self, query: Q, predictions: Sequence[P]) -> P:
        raise NotImplementedError
