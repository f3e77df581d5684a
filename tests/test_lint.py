"""Tests for the TPU-aware static analyzer (`pio lint`).

One positive + one negative fixture per rule family, suppression mechanics,
CLI surface, and the tier-1 self-lint gate: the repo's own package must
report zero unsuppressed errors.
"""

import os
import textwrap
import time

import pytest

from predictionio_tpu.analysis import (
    EntryPoint,
    LintConfig,
    Severity,
    all_rules,
    analyze_paths,
    analyze_source,
)
from predictionio_tpu.analysis.cli import default_lint_paths, main as lint_main

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.join(REPO_ROOT, "predictionio_tpu")


def lint_snippet(source, display_path="snippet.py", config=None):
    active, suppressed = analyze_source(
        textwrap.dedent(source), display_path, config=config
    )
    return active, suppressed


def rule_ids(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# family 1: tracer safety
# ---------------------------------------------------------------------------


class TestTracerRules:
    def test_branch_on_traced_param_fires(self):
        active, _ = lint_snippet(
            """
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
            """
        )
        assert rule_ids(active) == ["tracer-python-branch"]
        assert active[0].severity == Severity.ERROR

    def test_branch_on_static_arg_quiet(self):
        active, _ = lint_snippet(
            """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("mode",))
            def f(x, mode):
                if mode == "relu":
                    return x * (x > 0)
                return x
            """
        )
        assert active == []

    def test_while_on_alias_of_traced_fires(self):
        active, _ = lint_snippet(
            """
            import jax

            @jax.jit
            def f(x):
                y = x * 2
                while y.sum() > 0:
                    y = y - 1
                return y
            """
        )
        assert rule_ids(active) == ["tracer-python-branch"]

    def test_shape_branch_and_none_check_quiet(self):
        active, _ = lint_snippet(
            """
            import jax

            @jax.jit
            def f(x, bias=None):
                if x.shape[0] > 128:
                    x = x[:128]
                if bias is not None:
                    x = x + bias
                assert x.ndim == 2
                return x
            """
        )
        assert active == []

    def test_host_cast_fires(self):
        active, _ = lint_snippet(
            """
            import jax

            @jax.jit
            def f(x):
                return float(x) + x.sum().item()
            """
        )
        assert sorted(rule_ids(active)) == ["tracer-host-cast", "tracer-host-cast"]

    def test_host_cast_of_static_quiet(self):
        active, _ = lint_snippet(
            """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnums=(1,))
            def f(x, n):
                return x * int(n)
            """
        )
        assert active == []


# ---------------------------------------------------------------------------
# family 2: recompile hazards
# ---------------------------------------------------------------------------


class TestRecompileRules:
    def test_literal_arg_not_static_fires(self):
        active, _ = lint_snippet(
            """
            import jax

            @jax.jit
            def f(x, flag):
                return x

            def caller(v):
                return f(v, True)
            """
        )
        assert rule_ids(active) == ["recompile-unhashable-arg"]
        assert active[0].severity == Severity.WARNING

    def test_literal_arg_declared_static_quiet(self):
        active, _ = lint_snippet(
            """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("flag",))
            def f(x, flag):
                return x

            def caller(v):
                return f(v, flag=True)
            """
        )
        assert active == []

    def test_static_argnames_covers_positional_call_quiet(self):
        # JAX resolves static_argnames for positionally-passed args too
        active, _ = lint_snippet(
            """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("flag",))
            def f(x, flag):
                return x

            def caller(v):
                return f(v, True)
            """
        )
        assert active == []

    def test_jit_in_loop_fires(self):
        active, _ = lint_snippet(
            """
            import jax

            def serve(requests, fn):
                for r in requests:
                    jitted = jax.jit(fn)
                    yield jitted(r)
            """
        )
        assert rule_ids(active) == ["recompile-jit-in-loop"]

    def test_jit_hoisted_out_of_loop_quiet(self):
        active, _ = lint_snippet(
            """
            import jax

            def serve(requests, fn):
                jitted = jax.jit(fn)
                for r in requests:
                    yield jitted(r)
            """
        )
        assert active == []

    def test_closure_over_mutable_fires(self):
        active, _ = lint_snippet(
            """
            import jax

            def make(cfg_items):
                cfg = {}
                cfg.update(cfg_items)

                @jax.jit
                def predict(x):
                    return x * cfg["scale"]

                return predict
            """
        )
        assert rule_ids(active) == ["recompile-closure-capture"]

    def test_closure_over_immutable_quiet(self):
        active, _ = lint_snippet(
            """
            import jax

            def make(scale):
                @jax.jit
                def predict(x):
                    return x * scale

                return predict
            """
        )
        assert active == []


# ---------------------------------------------------------------------------
# family 3: host-sync stalls on the serving path
# ---------------------------------------------------------------------------

SYNC_SNIPPET = """
import numpy as np

def handle(pred):
    return np.asarray(pred).tolist()
"""


class TestHostSyncRules:
    def test_sync_in_serving_module_fires(self):
        active, _ = lint_snippet(
            SYNC_SNIPPET, display_path="predictionio_tpu/data/api/handlers.py"
        )
        assert rule_ids(active) == ["hostsync-serving-path"]
        assert active[0].severity == Severity.ERROR

    def test_same_code_off_serving_path_quiet(self):
        active, _ = lint_snippet(
            SYNC_SNIPPET, display_path="predictionio_tpu/ops/score.py"
        )
        assert active == []

    def test_block_until_ready_fires(self):
        active, _ = lint_snippet(
            """
            import jax

            def handle(pred):
                jax.block_until_ready(pred)
                return pred
            """,
            display_path="predictionio_tpu/controller/serving.py",
        )
        assert rule_ids(active) == ["hostsync-serving-path"]

    def test_serving_match_is_cwd_independent(self, tmp_path, monkeypatch):
        # the glob must key on the real path: linting from inside the tree
        # (display path loses leading components) must not disable the rule
        api = tmp_path / "pkg" / "data" / "api"
        api.mkdir(parents=True)
        (api / "handlers.py").write_text(textwrap.dedent(SYNC_SNIPPET))
        monkeypatch.chdir(tmp_path / "pkg" / "data")
        report = analyze_paths(["api"])
        assert rule_ids(report.findings) == ["hostsync-serving-path"]

    def test_function_outside_declared_entry_points_quiet(self):
        # the old allow-list is gone: scoping is declared at the entry
        # points now. With only `handle` declared as the serving entry,
        # an unreachable `warmup` in the same module stays quiet.
        entries = (
            EntryPoint("serving", "*/controller/serving.py", function="handle"),
        )
        src = """
            import jax

            def handle(model):
                jax.block_until_ready(model)

            def warmup(model):
                jax.block_until_ready(model)
            """
        active, _ = lint_snippet(
            src,
            display_path="predictionio_tpu/controller/serving.py",
            config=LintConfig(entry_points=entries),
        )
        assert rule_ids(active) == ["hostsync-serving-path"]
        assert active[0].message.count("'handle'")


# ---------------------------------------------------------------------------
# family 4: concurrency
# ---------------------------------------------------------------------------


class TestConcurrencyRules:
    def test_unlocked_global_mutation_fires(self):
        active, _ = lint_snippet(
            """
            import threading

            _stats = {}

            def serve():
                threading.Thread(target=work).start()

            def work():
                _stats["n"] = _stats.get("n", 0) + 1
            """
        )
        assert rule_ids(active) == ["concurrency-unlocked-global"]
        assert active[0].severity == Severity.WARNING

    def test_locked_mutation_quiet(self):
        active, _ = lint_snippet(
            """
            import threading

            _stats = {}
            _lock = threading.Lock()

            def serve():
                threading.Thread(target=work).start()

            def work():
                with _lock:
                    _stats["n"] = _stats.get("n", 0) + 1
            """
        )
        assert active == []

    def test_unthreaded_module_quiet(self):
        active, _ = lint_snippet(
            """
            _stats = {}

            def work():
                _stats["n"] = 1
            """
        )
        assert active == []


# ---------------------------------------------------------------------------
# family 5: storage contract
# ---------------------------------------------------------------------------

BASE_PY = """
import abc


class Apps(abc.ABC):
    @abc.abstractmethod
    def insert(self, app): ...

    @abc.abstractmethod
    def get(self, app_id): ...

    @abc.abstractmethod
    def delete(self, app_id): ...
"""


class TestStorageContractRule:
    def _write_backend(self, tmp_path, body):
        storage = tmp_path / "storage"
        storage.mkdir()
        (storage / "base.py").write_text(textwrap.dedent(BASE_PY))
        (storage / "backend.py").write_text(textwrap.dedent(body))
        return str(storage)

    def test_missing_method_fires(self, tmp_path):
        path = self._write_backend(
            tmp_path,
            """
            from .base import Apps

            class PartialApps(Apps):
                def insert(self, app):
                    return 1
            """,
        )
        report = analyze_paths([path])
        assert rule_ids(report.findings) == ["storage-missing-method"]
        assert "delete" in report.findings[0].message
        assert "get" in report.findings[0].message

    def test_full_surface_quiet(self, tmp_path):
        path = self._write_backend(
            tmp_path,
            """
            from . import base

            class FullApps(base.Apps):
                def insert(self, app):
                    return 1

                def get(self, app_id):
                    return None

                def delete(self, app_id):
                    pass
            """,
        )
        report = analyze_paths([path])
        assert report.findings == []

    def test_local_intermediate_base_counts(self, tmp_path):
        path = self._write_backend(
            tmp_path,
            """
            from .base import Apps

            class _Common(Apps):
                def get(self, app_id):
                    return None

                def delete(self, app_id):
                    pass

            class DerivedApps(_Common):
                def insert(self, app):
                    return 1
            """,
        )
        report = analyze_paths([path])
        # _Common alone is missing insert; DerivedApps completes the surface
        assert [f.message.split("'")[1] for f in report.findings] == ["_Common"]


# ---------------------------------------------------------------------------
# family: stream path (speed layer)
# ---------------------------------------------------------------------------


class TestStreamRules:
    def test_unbounded_find_after_fires(self):
        active, _ = lint_snippet(
            """
            def drain(levents, app):
                return levents.find_after(app, cursor=None)
            """,
            display_path="pkg/stream/tailer.py",
        )
        assert rule_ids(active) == ["stream-unbounded-drain"]

    def test_unbounded_dao_find_fires(self):
        active, _ = lint_snippet(
            """
            def catch_up(levents):
                return list(levents.find(app_id=1, event_names=["rate"]))
            """,
            display_path="pkg/stream/pipeline.py",
        )
        assert rule_ids(active) == ["stream-unbounded-drain"]

    def test_bounded_reads_quiet(self):
        active, _ = lint_snippet(
            """
            def drain(levents, app, cursor):
                a = levents.find_after(app, cursor=cursor, limit=100)
                b = levents.find(app_id=app, limit=50)
                return a, b
            """,
            display_path="pkg/stream/tailer.py",
        )
        assert active == []

    def test_str_find_and_off_path_reads_quiet(self):
        # str.find is not an event-store read; and the same unbounded DAO
        # read OUTSIDE the stream path is another rule's problem
        active, _ = lint_snippet(
            """
            def misc(levents, name):
                idx = name.find(":")
                return idx
            """,
            display_path="pkg/stream/util.py",
        )
        assert active == []
        active, _ = lint_snippet(
            """
            def batch_read(levents):
                return list(levents.find(app_id=1))
            """,
            display_path="pkg/workflow/train.py",
        )
        assert active == []

    def test_limit_none_is_still_unbounded(self):
        active, _ = lint_snippet(
            """
            def drain(levents, app):
                return levents.find_after(app, cursor=None, limit=None)
            """,
            display_path="pkg/stream/tailer.py",
        )
        assert rule_ids(active) == ["stream-unbounded-drain"]


class TestTrainSyncRule:
    def test_bare_syncs_fire_in_train_module(self):
        active, _ = lint_snippet(
            """
            import jax
            import numpy as np

            def train_loop(dev_arrays, x):
                jax.block_until_ready(x)
                host = np.asarray(x)
                scalar = x.item()
                return host, scalar
            """,
            display_path="pkg/ops/als.py",
        )
        assert rule_ids(active) == ["train-unaccounted-sync"] * 3

    def test_two_arg_asarray_is_host_conversion_quiet(self):
        # np.asarray(x, np.float32) is this codebase's HOST-input
        # conversion idiom; the bare one-arg form is the device readback
        active, _ = lint_snippet(
            """
            import numpy as np

            def prep(ratings):
                return np.asarray(ratings, np.float32)
            """,
            display_path="pkg/ops/als.py",
        )
        assert active == []

    def test_sanctioned_forms_quiet(self):
        active, _ = lint_snippet(
            """
            from predictionio_tpu.obs import xray
            from predictionio_tpu.obs.jaxprof import timed_block_until_ready

            def train_loop(x, registry):
                timed_block_until_ready(x, registry, where="sweep")
                return xray.device_fetch(x, where="sweep")
            """,
            display_path="pkg/stream/trainers.py",
        )
        assert active == []

    def test_same_code_off_train_path_quiet(self):
        active, _ = lint_snippet(
            """
            import jax

            def bench(x):
                jax.block_until_ready(x)
            """,
            display_path="pkg/eval/fast_eval.py",
        )
        assert active == []

    def test_suppression_with_reason_works(self):
        active, suppressed = lint_snippet(
            """
            import numpy as np

            def barrier(checksum):
                # pio-lint: disable=train-unaccounted-sync -- this IS the instrument
                return float(np.asarray(checksum))
            """,
            display_path="pkg/ops/als.py",
        )
        assert active == []
        assert rule_ids(suppressed) == ["train-unaccounted-sync"]


class TestServingRoundtripRule:
    def test_host_argsort_and_full_fetch_fire_on_predict_path(self):
        active, _ = lint_snippet(
            """
            import numpy as np

            def predict(model, query):
                scores = np.asarray(model.device_scores)
                idx = np.argsort(-scores)
                return idx[: query.num]
            """,
            display_path="pkg/models/foo/engine.py",
        )
        assert rule_ids(active) == ["serving-host-roundtrip"] * 2
        assert all(f.severity == Severity.ERROR for f in active)

    def test_nested_finalize_is_covered(self):
        # the dispatch pattern hides the fetch inside a closure — the rule
        # must walk nested functions of the predict-path entry points
        active, _ = lint_snippet(
            """
            import numpy as np

            def predict_batch_dispatch(model, queries):
                handle = model.dispatch(queries)

                def finalize():
                    return np.argpartition(-np.asarray(handle), 10)

                return finalize
            """,
            display_path="pkg/models/foo/engine.py",
        )
        assert rule_ids(active) == ["serving-host-roundtrip"] * 2

    def test_fused_helper_and_host_topk_quiet(self):
        active, _ = lint_snippet(
            """
            import numpy as np
            from predictionio_tpu.ops import topk

            def predict_batch_dispatch(model, queries):
                handle = topk.dot_top_k_async(
                    model.table, model.vecs, None, 10
                )

                def finalize():
                    scores, idx = topk.fetch_topk(handle)
                    sk, si = topk.host_top_k(model.counts, None, 10)
                    return scores, idx, sk, si

                return finalize
            """,
            display_path="pkg/models/foo/engine.py",
        )
        assert active == []

    def test_two_arg_asarray_host_idiom_quiet(self):
        active, _ = lint_snippet(
            """
            import numpy as np

            def predict(model, query):
                vec = np.asarray(query.features, np.float32)
                return model.score(vec)
            """,
            display_path="pkg/models/foo/engine.py",
        )
        assert active == []

    def test_training_code_in_engine_module_quiet(self):
        # the rule scopes to the predict path, not the whole module: a
        # trainer materializing factors host-side is the train rule's
        # business (different globs), not a serving roundtrip
        active, _ = lint_snippet(
            """
            import numpy as np

            def train(ctx, data):
                return np.asarray(data.factors)
            """,
            display_path="pkg/models/foo/engine.py",
        )
        assert active == []

    def test_same_code_outside_engine_globs_quiet(self):
        active, _ = lint_snippet(
            """
            import numpy as np

            def predict(model, query):
                return np.argsort(-np.asarray(model.scores))
            """,
            display_path="pkg/eval/fast_eval.py",
        )
        assert active == []

    def test_offline_dispatch_path_covered(self):
        # ISSUE 14: the mega-batch pipeline (workflow/batch_predict.py +
        # Engine.dispatch_batch) dispatches the same fused kernels at
        # device-saturating batch sizes — a per-item device_get or host
        # argsort sneaking back in must fire the rule there too
        active, _ = lint_snippet(
            """
            import numpy as np

            def run_pipeline(engine, components, models, source, sinks):
                def drain(pending):
                    scores = np.asarray(pending.handle)
                    return np.argsort(-scores)

                return drain
            """,
            display_path="pkg/workflow/batch_predict.py",
        )
        assert rule_ids(active) == ["serving-host-roundtrip"] * 2

    def test_engine_dispatch_batch_covered(self):
        active, _ = lint_snippet(
            """
            import numpy as np

            def dispatch_batch(self, algorithms, serving, models, queries):
                def finalize():
                    return np.argpartition(-np.asarray(models[0].scores), 10)

                return finalize
            """,
            display_path="pkg/controller/engine.py",
        )
        assert rule_ids(active) == ["serving-host-roundtrip"] * 2

    def test_tuning_scoring_path_covered(self):
        # ISSUE 15: the evaluation grid's cell scoring rides the same
        # fused mega-batch contract — globs extended to tuning/*.py.
        # (tuning is ALSO in train_globs, so the bare one-arg asarray
        # additionally fires train-unaccounted-sync — both rails hold.)
        active, _ = lint_snippet(
            """
            import numpy as np

            def dispatch_scores(engine, algos, serving, models, queries):
                scores = np.asarray(models[0].device_scores)
                return np.argsort(-scores)
            """,
            display_path="pkg/tuning/cells.py",
        )
        ids = rule_ids(active)
        assert ids.count("serving-host-roundtrip") == 2
        assert "train-unaccounted-sync" in ids


class TestEvalPerQueryPredictRule:
    """ISSUE 15 acceptance: no per-query predict loop on the grid's
    scoring path — held statically."""

    def test_predict_loop_in_scoring_fires(self):
        active, _ = lint_snippet(
            """
            def dispatch_scores(engine, algos, serving, models, queries):
                return [algos[0].predict(models[0], q) for q in queries]
            """,
            display_path="pkg/tuning/cells.py",
        )
        assert rule_ids(active) == ["eval-per-query-predict"]
        assert active[0].severity == Severity.ERROR

    def test_nested_helper_covered(self):
        active, _ = lint_snippet(
            """
            def score_cell(self, key):
                def slow_path():
                    return [self.algo.predict(self.model, q) for q in self.qs]

                return slow_path()
            """,
            display_path="pkg/tuning/cells.py",
        )
        assert rule_ids(active) == ["eval-per-query-predict"]

    def test_batched_entries_quiet(self):
        active, _ = lint_snippet(
            """
            def dispatch_scores(engine, algos, serving, models, queries):
                fin = engine.dispatch_batch(algos, serving, models, queries)
                extra = algos[0].predict_batch(models[0], queries)
                more = algos[0].batch_predict(models[0], list(enumerate(queries)))
                return fin() + extra + more
            """,
            display_path="pkg/tuning/cells.py",
        )
        assert active == []

    def test_outside_scoring_functions_quiet(self):
        # the rule scopes to the scoring path, not the whole module: a
        # diagnostic helper may predict one query
        active, _ = lint_snippet(
            """
            def debug_one(algo, model, q):
                return algo.predict(model, q)
            """,
            display_path="pkg/tuning/cells.py",
        )
        assert active == []

    def test_outside_tuning_quiet(self):
        active, _ = lint_snippet(
            """
            def dispatch_scores(engine, algos, serving, models, queries):
                return [algos[0].predict(models[0], q) for q in queries]
            """,
            display_path="pkg/eval/evaluator.py",
        )
        assert active == []


# ---------------------------------------------------------------------------
# engine mechanics: suppression, severity, parse errors
# ---------------------------------------------------------------------------


class TestSuppression:
    BAD = """
    import jax

    @jax.jit
    def f(x):
        if x > 0:  # pio-lint: disable=tracer-python-branch -- fixture
            return x
        return -x
    """

    def test_inline_suppression(self):
        active, suppressed = lint_snippet(self.BAD)
        assert active == []
        assert rule_ids(suppressed) == ["tracer-python-branch"]

    def test_suppression_comment_on_previous_line(self):
        active, suppressed = lint_snippet(
            """
            import jax

            @jax.jit
            def f(x):
                # pio-lint: disable=tracer-python-branch -- fixture
                if x > 0:
                    return x
                return -x
            """
        )
        assert active == []
        assert len(suppressed) == 1

    def test_file_level_suppression(self):
        active, suppressed = lint_snippet(
            """
            # pio-lint: disable-file=tracer-python-branch
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
            """
        )
        assert active == []
        assert len(suppressed) == 1

    def test_wrong_rule_id_does_not_suppress(self):
        active, _ = lint_snippet(
            """
            import jax

            @jax.jit
            def f(x):
                if x > 0:  # pio-lint: disable=tracer-host-cast
                    return x
                return -x
            """
        )
        # the finding still fires, AND the mismatched suppression is
        # called out as stale (it matched nothing this run)
        assert sorted(rule_ids(active)) == [
            "suppression-stale",
            "tracer-python-branch",
        ]


class TestObsRules:
    """obs-unstructured-log: print()/bare logging.* on serving-path modules
    must point at the structured trace logger."""

    SERVING_PATH = "pkg/data/api/handler.py"  # matches */data/api/*.py

    def test_print_on_serving_path_fires(self):
        active, _ = lint_snippet(
            """
            def handle(request):
                print("got", request)
                return request
            """,
            display_path=self.SERVING_PATH,
        )
        assert rule_ids(active) == ["obs-unstructured-log"]
        assert active[0].severity == Severity.WARNING
        assert "trace logger" in active[0].message

    def test_bare_logging_on_serving_path_fires(self):
        active, _ = lint_snippet(
            """
            import logging

            def handle(request):
                logging.info("handling %s", request)
                logging.error("boom")
            """,
            display_path=self.SERVING_PATH,
        )
        assert rule_ids(active) == [
            "obs-unstructured-log",
            "obs-unstructured-log",
        ]

    def test_named_logger_quiet(self):
        active, _ = lint_snippet(
            """
            import logging

            logger = logging.getLogger(__name__)

            def handle(request):
                logger.info("handling %s", request)
            """,
            display_path=self.SERVING_PATH,
        )
        assert active == []

    def test_print_off_serving_path_quiet(self):
        active, _ = lint_snippet(
            """
            def train_loop():
                print("epoch done")
            """,
            display_path="pkg/tools/cli.py",
        )
        assert active == []

    def test_suppressible_with_reason(self):
        active, suppressed = lint_snippet(
            """
            def handle(request):
                print("x")  # pio-lint: disable=obs-unstructured-log -- startup banner
            """,
            display_path=self.SERVING_PATH,
        )
        assert active == []
        assert rule_ids(suppressed) == ["obs-unstructured-log"]


class TestObsLabelCardinality:
    """obs-label-cardinality: metric label values derived from per-request
    data (query/user/entity ids) on the serving path mint one timeseries
    per distinct value — the classic slow leak."""

    SERVING_PATH = "pkg/data/api/handler.py"  # matches */data/api/*.py

    def test_per_request_label_fires(self):
        active, _ = lint_snippet(
            """
            def handle(counter, query):
                counter.inc(user=query["user"])
            """,
            display_path=self.SERVING_PATH,
        )
        assert rule_ids(active) == ["obs-label-cardinality"]
        assert active[0].severity == Severity.WARNING
        assert "user" in active[0].message

    def test_attribute_derived_label_fires(self):
        active, _ = lint_snippet(
            """
            def handle(hist, event):
                hist.observe(0.5, entity=event.entity_id)
            """,
            display_path=self.SERVING_PATH,
        )
        assert rule_ids(active) == ["obs-label-cardinality"]

    def test_constant_and_bounded_labels_quiet(self):
        active, _ = lint_snippet(
            """
            def handle(counter, status, endpoint):
                counter.inc(endpoint="/queries.json", status=str(status))
                counter.inc(endpoint=endpoint, status="200")
            """,
            display_path=self.SERVING_PATH,
        )
        assert active == []

    def test_exemplar_kwarg_quiet(self):
        # exemplars are DESIGNED to carry per-request trace ids (bounded:
        # one per histogram bucket) — never a label
        active, _ = lint_snippet(
            """
            def handle(hist, trace_id):
                hist.observe(0.01, exemplar=trace_id, phase="fetch")
            """,
            display_path=self.SERVING_PATH,
        )
        assert active == []

    def test_off_serving_path_quiet(self):
        active, _ = lint_snippet(
            """
            def report(counter, query):
                counter.inc(user=query["user"])
            """,
            display_path="pkg/tools/cli.py",
        )
        assert active == []

    def test_positional_args_quiet(self):
        # only keyword arguments are label values on the metric API
        active, _ = lint_snippet(
            """
            def handle(hist, query_seconds):
                hist.observe(query_seconds)
            """,
            display_path=self.SERVING_PATH,
        )
        assert active == []

    def test_suppressible_with_reason(self):
        active, suppressed = lint_snippet(
            """
            def handle(counter, event):
                counter.inc(event=event.event)  # pio-lint: disable=obs-label-cardinality -- bounded by app schema
            """,
            display_path=self.SERVING_PATH,
        )
        assert active == []
        assert rule_ids(suppressed) == ["obs-label-cardinality"]


class TestEngine:
    def test_parse_error_reported_not_raised(self):
        active, _ = lint_snippet("def broken(:\n")
        assert rule_ids(active) == ["parse-error"]

    def test_rule_registry_covers_all_families(self):
        families = {m.family for m in all_rules()}
        assert {
            "tracer",
            "recompile",
            "hostsync",
            "concurrency",
            "storage-contract",
            "obs",
            "fleet",
            "mesh",
            "async",
            "engine",
        } <= families

    def test_enabled_filter(self):
        active, _ = lint_snippet(
            """
            import jax

            @jax.jit
            def f(x):
                return float(x) if False else -x
            """,
            config=LintConfig(enabled=frozenset({"tracer-python-branch"})),
        )
        assert all(f.rule == "tracer-python-branch" for f in active)


# ---------------------------------------------------------------------------
# CLI + the tier-1 self-lint gate
# ---------------------------------------------------------------------------


class TestCLI:
    def test_list_rules(self, capsys):
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        assert "tracer-python-branch" in out
        assert "storage-missing-method" in out

    def test_exit_one_on_error_finding(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "import jax\n\n@jax.jit\ndef f(x):\n    if x > 0:\n        return x\n"
            "    return -x\n"
        )
        assert lint_main([str(bad)]) == 1
        assert "tracer-python-branch" in capsys.readouterr().out

    def test_warnings_pass_unless_strict(self, tmp_path, capsys):
        warn = tmp_path / "warn.py"
        warn.write_text(
            "import jax\n\ndef serve(reqs, fn):\n    for r in reqs:\n"
            "        jax.jit(fn)(r)\n"
        )
        assert lint_main([str(warn)]) == 0
        assert lint_main(["--strict", str(warn)]) == 1
        capsys.readouterr()

    def test_json_format(self, tmp_path, capsys):
        import json

        bad = tmp_path / "bad.py"
        bad.write_text(
            "import jax\n\n@jax.jit\ndef f(x):\n    return int(x)\n"
        )
        assert lint_main(["--format", "json", str(bad)]) == 1
        data = json.loads(capsys.readouterr().out)
        assert data["files_scanned"] == 1
        assert data["findings"][0]["rule"] == "tracer-host-cast"

    def test_missing_path_exits_two(self, capsys):
        assert lint_main(["/nonexistent/nowhere.py"]) == 2
        capsys.readouterr()

    def test_unknown_rule_id_exits_two(self, tmp_path, capsys):
        # a typo'd --rule must not silently disable the gate
        ok = tmp_path / "ok.py"
        ok.write_text("x = 1\n")
        assert lint_main(["--rule", "tracer-pythn-branch", str(ok)]) == 2
        assert "unknown rule id" in capsys.readouterr().err

    def test_pio_lint_subcommand(self, tmp_path, capsys):
        from predictionio_tpu.tools.cli import main as pio_main

        bad = tmp_path / "bad.py"
        bad.write_text(
            "import jax\n\n@jax.jit\ndef f(x):\n    assert x > 0\n    return x\n"
        )
        assert pio_main(["lint", str(bad)]) == 1
        assert "tracer-python-branch" in capsys.readouterr().out


class TestSelfLint:
    def test_package_lints_clean(self, capsys):
        """The tier-1 gate: the repo's own code has zero unsuppressed
        error-severity findings, and the whole-program walk (cross-file
        call graph included) stays under the 8s budget (was 5s when the
        package had ~160 files; the sequential + bandit subsystems grew
        the walk to ~180 and the old budget became a coin flip on the
        1-core sandbox — the point of the gate is catching superlinear
        blowups, which overshoot any constant budget). Best of two
        timings: a full-suite run shares the box with other tests, and
        scheduler contention is not a lint regression (a real one fails
        both measurements)."""
        start = time.monotonic()
        rc = lint_main([PKG_DIR])
        elapsed = time.monotonic() - start
        out = capsys.readouterr().out
        assert rc == 0, f"self-lint found errors:\n{out}"
        if elapsed >= 8.0:
            start = time.monotonic()
            assert lint_main([PKG_DIR]) == 0
            elapsed = min(elapsed, time.monotonic() - start)
            capsys.readouterr()
        assert elapsed < 8.0, f"self-lint took {elapsed:.1f}s (budget 8s)"

    def test_lint_never_imports_accelerator_runtime(self):
        """`pio lint` runs in pre-commit and CI where importing jax/numpy
        (or touching the accelerator) is exactly what it must avoid —
        asserted in a clean interpreter so a stray transitive import
        can't hide behind the test process's own modules."""
        import subprocess
        import sys

        code = (
            "import sys\n"
            "from predictionio_tpu.analysis import analyze_paths\n"
            f"r = analyze_paths([{PKG_DIR!r}])\n"
            "assert not r.errors, [f.format() for f in r.errors]\n"
            "bad = [m for m in ('jax', 'numpy') if m in sys.modules]\n"
            "assert not bad, f'lint imported accelerator runtime: {bad}'\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,
        )
        assert proc.returncode == 0, proc.stderr

    def test_default_paths_cover_package_and_examples(self):
        paths = default_lint_paths()
        assert any(p.endswith("predictionio_tpu") for p in paths)
        report = analyze_paths(paths)
        # the walk must actually visit the tree, not silently skip it
        assert report.files_scanned > 80
        assert report.errors == []


# ---------------------------------------------------------------------------
# family: storage-contract — raw pickle boundary
# ---------------------------------------------------------------------------


class TestStorageRawPickle:
    SRC = """
        import pickle

        def read_model(blob):
            return pickle.loads(blob)
    """

    def test_raw_pickle_fires_outside_boundary(self):
        active, _ = lint_snippet(
            self.SRC, "predictionio_tpu/data/storage/sqlite.py"
        )
        assert "storage-raw-pickle" in rule_ids(active)

    def test_module_alias_form_fires(self):
        active, _ = lint_snippet(
            """
            import pickle as pkl

            def read_model(blob):
                return pkl.loads(blob)
            """,
            "predictionio_tpu/data/storage/sqlite.py",
        )
        assert "storage-raw-pickle" in rule_ids(active)

    def test_bare_import_form_fires(self):
        active, _ = lint_snippet(
            """
            from pickle import loads

            def read_model(blob):
                return loads(blob)
            """,
            "predictionio_tpu/tools/shell.py",
        )
        assert "storage-raw-pickle" in rule_ids(active)

    def test_model_io_and_registry_store_are_the_allowed_boundary(self):
        for allowed in (
            "predictionio_tpu/workflow/model_io.py",
            "predictionio_tpu/registry/store.py",
        ):
            active, _ = lint_snippet(self.SRC, allowed)
            assert "storage-raw-pickle" not in rule_ids(active)

    def test_other_loads_names_quiet(self):
        active, _ = lint_snippet(
            """
            import json
            from msgpack import loads as m_loads

            def read(blob):
                return json.loads(blob) or m_loads(blob)
            """,
            "predictionio_tpu/data/storage/sqlite.py",
        )
        assert "storage-raw-pickle" not in rule_ids(active)


class TestFleetUnattributedProxy:
    """fleet-unattributed-proxy: outbound replica calls and replica state
    transitions in the fleet gateway/supervisor must route through the
    span/telemetry helpers — an unattributed proxy is a hop the merged
    /traces/recent can never assemble, an unattributed eject/park is
    evidence the incident flight recorder never sees."""

    FLEET_PATH = "predictionio_tpu/fleet/gateway.py"

    def test_bare_session_call_fires(self):
        active, _ = lint_snippet(
            """
            async def forward(self, replica, body):
                async with self._http().request("POST", replica.url, data=body) as r:
                    return await r.read()
            """,
            self.FLEET_PATH,
        )
        assert rule_ids(active) == ["fleet-unattributed-proxy"]
        assert active[0].severity == Severity.ERROR
        assert "span" in active[0].message

    def test_span_wrapped_call_quiet(self):
        active, _ = lint_snippet(
            """
            async def forward(self, replica, body):
                with self.tracer.span("gateway.proxy", kind="gateway"):
                    async with self._http().request("POST", replica.url) as r:
                        return await r.read()
            """,
            self.FLEET_PATH,
        )
        assert active == []

    def test_record_span_after_call_quiet(self):
        active, _ = lint_snippet(
            """
            async def forward(self, replica):
                t0 = time.perf_counter()
                async with self._http().get(replica.url) as r:
                    body = await r.read()
                self.tracer.record_span("gateway.proxy", "gateway", 1.0)
                return body
            """,
            self.FLEET_PATH,
        )
        assert active == []

    def test_unattributed_state_transition_fires(self):
        active, _ = lint_snippet(
            """
            def on_probe(self, replica, ok):
                if not ok:
                    replica.healthy = False
            """,
            self.FLEET_PATH,
        )
        assert rule_ids(active) == ["fleet-unattributed-proxy"]
        assert "transition" in active[0].message

    def test_transition_via_note_helper_quiet(self):
        active, _ = lint_snippet(
            """
            def on_probe(self, replica, ok):
                if not ok:
                    replica.healthy = False
                    self._note_transition("eject", replica)
            """,
            self.FLEET_PATH,
        )
        assert active == []

    def test_transition_with_counter_quiet(self):
        active, _ = lint_snippet(
            """
            def record_crash(self, w):
                w.parked = True
                self._m_crash_loops.inc(replica=w.spec.name)
            """,
            "predictionio_tpu/fleet/supervisor.py",
        )
        assert active == []

    def test_init_constructing_state_quiet(self):
        active, _ = lint_snippet(
            """
            class Replica:
                def __init__(self, url):
                    self.healthy = True
            """,
            self.FLEET_PATH,
        )
        assert active == []

    def test_unattributed_retire_transition_fires(self):
        """Scale-in is a fleet transition too: setting a worker retiring
        without telemetry attribution hides the drain timeline."""
        active, _ = lint_snippet(
            """
            def retire(self, w):
                w.retiring = True
                w.proc.terminate()
            """,
            "predictionio_tpu/fleet/supervisor.py",
        )
        assert rule_ids(active) == ["fleet-unattributed-proxy"]

    def test_attributed_retire_quiet(self):
        active, _ = lint_snippet(
            """
            def retire(self, w):
                w.retiring = True
                self._m_retired.inc(worker_class=w.spec.worker_class)
            """,
            "predictionio_tpu/fleet/supervisor.py",
        )
        assert active == []

    def test_autoscaler_module_in_scope(self):
        """fleet/autoscaler.py rides the same rule: a scaling action that
        flips replica state without attribution is invisible telemetry."""
        active, _ = lint_snippet(
            """
            def force_eject(self, replica):
                replica.healthy = False
            """,
            "predictionio_tpu/fleet/autoscaler.py",
        )
        assert rule_ids(active) == ["fleet-unattributed-proxy"]

    def test_off_fleet_path_quiet(self):
        active, _ = lint_snippet(
            """
            async def fetch(self, session, url):
                async with session.get(url) as r:
                    return await r.read()
            """,
            "predictionio_tpu/tools/dashboard.py",
        )
        assert "fleet-unattributed-proxy" not in rule_ids(active)

    def test_suppressible_with_reason(self):
        active, suppressed = lint_snippet(
            """
            async def fetch_metrics(self, replica):
                # pio-lint: disable=fleet-unattributed-proxy -- telemetry plane fetch
                async with self._http().get(replica.url) as r:
                    return await r.text()
            """,
            self.FLEET_PATH,
        )
        assert active == []
        assert rule_ids(suppressed) == ["fleet-unattributed-proxy"]

    def test_nested_helper_judged_on_its_own(self):
        # the outer fn records a span, but the nested helper makes the
        # call without attribution of its own — still flagged
        active, _ = lint_snippet(
            """
            async def outer(self, replica):
                self.tracer.record_span("x", "gateway", 0.0)

                async def inner():
                    async with self._http().get(replica.url) as r:
                        return await r.read()

                return await inner()
            """,
            self.FLEET_PATH,
        )
        assert rule_ids(active) == ["fleet-unattributed-proxy"]

    def test_nested_attribution_does_not_vouch_for_outer(self):
        # symmetric blindness: a span recorded inside a NESTED helper
        # must not silence an unattributed call in the OUTER function
        active, _ = lint_snippet(
            """
            async def outer(self, replica):
                def unrelated_helper():
                    self.tracer.record_span("x", "gateway", 0.0)

                async with self._http().get(replica.url) as r:
                    return await r.read()
            """,
            self.FLEET_PATH,
        )
        assert rule_ids(active) == ["fleet-unattributed-proxy"]


# ---------------------------------------------------------------------------
# ISSUE 16: whole-program reachability (cross-file call graph)
# ---------------------------------------------------------------------------


def _write_tree(root, files):
    """Lay out {relpath: source} under root and return str(root)."""
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(root)


class TestCallGraphReachability:
    def test_violation_two_calls_below_entry_in_unnamed_module(self, tmp_path):
        """The acceptance fixture: the sync lives in a module NO glob
        names, two calls below a declared serving entry — only computed
        reachability can find it."""
        root = _write_tree(
            tmp_path,
            {
                "pkg/data/api/handlers.py": """
                    from pkg.util.mid import respond

                    async def handle(req):
                        return respond(req)
                    """,
                "pkg/util/mid.py": """
                    from pkg.util.deep import fetch

                    def respond(req):
                        return fetch(req)
                    """,
                "pkg/util/deep.py": """
                    import numpy as np

                    def fetch(pred):
                        return np.asarray(pred).tolist()
                    """,
            },
        )
        report = analyze_paths([root])
        hits = [f for f in report.findings if f.rule == "hostsync-serving-path"]
        assert len(hits) == 1
        assert hits[0].path.endswith(os.path.join("util", "deep.py"))
        assert "reachable from entry point 'handle'" in hits[0].message

    def test_method_dispatch_reaches_class_helpers(self, tmp_path):
        root = _write_tree(
            tmp_path,
            {
                "pkg/data/api/handlers.py": """
                    from pkg.core.engine import Engine

                    async def handle(req):
                        eng = Engine()
                        return eng.respond(req)
                    """,
                "pkg/core/engine.py": """
                    import numpy as np

                    class Engine:
                        def respond(self, req):
                            return self._finish(req)

                        def _finish(self, req):
                            return np.asarray(req)
                    """,
            },
        )
        report = analyze_paths([root])
        hits = [f for f in report.findings if f.rule == "hostsync-serving-path"]
        assert len(hits) == 1
        assert hits[0].path.endswith("engine.py")

    def test_call_cycle_terminates_and_still_flags(self, tmp_path):
        root = _write_tree(
            tmp_path,
            {
                "pkg/data/api/handlers.py": """
                    from pkg.util.a import f

                    async def handle(req):
                        return f(req, 3)
                    """,
                "pkg/util/a.py": """
                    from pkg.util.b import g

                    def f(x, depth):
                        return g(x, depth)
                    """,
                "pkg/util/b.py": """
                    import numpy as np
                    from pkg.util.a import f

                    def g(x, depth):
                        if depth:
                            return f(x, depth - 1)
                        return np.asarray(x)
                    """,
            },
        )
        report = analyze_paths([root])
        hits = [f for f in report.findings if f.rule == "hostsync-serving-path"]
        assert len(hits) == 1
        assert hits[0].path.endswith("b.py")

    def test_unreachable_helper_module_quiet(self, tmp_path):
        # same helper module, but nothing on a declared entry path calls
        # it: reachability (not module globs) decides, so it stays quiet
        root = _write_tree(
            tmp_path,
            {
                "pkg/data/api/handlers.py": """
                    async def handle(req):
                        return req
                    """,
                "pkg/util/deep.py": """
                    import numpy as np

                    def fetch(pred):
                        return np.asarray(pred).tolist()
                    """,
            },
        )
        report = analyze_paths([root])
        assert report.findings == []


# ---------------------------------------------------------------------------
# ISSUE 16 family: mesh/sharding agreement
# ---------------------------------------------------------------------------


class TestMeshRules:
    DECL = """
        from jax.sharding import Mesh

        def build(devs):
            return Mesh(devs, ("data", "model"))
    """

    def test_unknown_partition_axis_fires(self, tmp_path):
        root = _write_tree(
            tmp_path,
            {
                "pkg/parallel/mesh.py": self.DECL,
                "pkg/parallel/kernel.py": """
                    from jax.sharding import PartitionSpec as P

                    def spec():
                        return P("data", "expert")
                    """,
            },
        )
        report = analyze_paths([root])
        hits = [f for f in report.findings if f.rule == "mesh-unknown-axis"]
        assert len(hits) == 1
        assert "'expert'" in hits[0].message

    def test_declared_axis_cross_module_quiet(self, tmp_path):
        root = _write_tree(
            tmp_path,
            {
                "pkg/parallel/mesh.py": self.DECL,
                "pkg/parallel/kernel.py": """
                    from jax.sharding import PartitionSpec as P

                    def spec():
                        return P("data", "model")
                    """,
            },
        )
        report = analyze_paths([root])
        assert report.findings == []

    def test_no_declarations_anywhere_stays_silent(self):
        active, _ = lint_snippet(
            """
            from jax.sharding import PartitionSpec as P

            def spec():
                return P("whatever")
            """,
            "predictionio_tpu/parallel/kernel.py",
        )
        assert active == []

    def test_collective_axis_mismatch_fires(self):
        active, _ = lint_snippet(
            """
            from jax import lax
            from jax.sharding import Mesh

            def build(devs):
                return Mesh(devs, ("data",))

            def reduce_shard(x):
                return lax.psum(x, "model")
            """,
            "predictionio_tpu/parallel/kernel.py",
        )
        assert rule_ids(active) == ["mesh-collective-axis"]

    def test_collective_declared_axis_and_variable_axis_quiet(self):
        active, _ = lint_snippet(
            """
            from jax import lax
            from jax.sharding import Mesh

            def build(devs):
                return Mesh(devs, ("data",))

            def reduce_shard(x, axis_var):
                a = lax.psum(x, "data")
                return lax.psum(a, axis_var)
            """,
            "predictionio_tpu/parallel/kernel.py",
        )
        assert active == []

    def test_spec_string_declaration_counts(self):
        active, _ = lint_snippet(
            """
            from jax import lax

            def build():
                return make_mesh("data=8,model=2")

            def reduce_shard(x):
                return lax.pmean(x, "model")
            """,
            "predictionio_tpu/parallel/kernel.py",
        )
        assert active == []

    def test_host_materialize_of_sharded_value_fires(self):
        active, _ = lint_snippet(
            """
            import numpy as np
            from jax.experimental.shard_map import shard_map

            def step(mesh, x, f):
                y = shard_map(f, mesh=mesh)(x)
                return np.asarray(y)
            """,
            "predictionio_tpu/parallel/ingest.py",
        )
        assert rule_ids(active) == ["mesh-host-materialize"]

    def test_two_arg_asarray_and_untainted_value_quiet(self):
        active, _ = lint_snippet(
            """
            import numpy as np
            from jax.experimental.shard_map import shard_map

            def step(mesh, x, f, host_rows):
                y = shard_map(f, mesh=mesh)(x)
                a = np.asarray(y, np.float32)
                b = np.asarray(host_rows)
                return a, b, y
            """,
            "predictionio_tpu/parallel/ingest.py",
        )
        assert active == []

    def test_materialize_outside_sharded_modules_quiet(self):
        active, _ = lint_snippet(
            """
            import numpy as np
            from jax.experimental.shard_map import shard_map

            def step(mesh, x, f):
                y = shard_map(f, mesh=mesh)(x)
                return np.asarray(y)
            """,
            "predictionio_tpu/tools/notebook_helpers.py",
        )
        assert active == []

    def test_topk_without_merge_fires(self):
        active, _ = lint_snippet(
            """
            from jax import lax

            def local_winners(scores, k):
                return lax.top_k(scores, k)
            """,
            "predictionio_tpu/ops/score_sharded.py",
        )
        assert rule_ids(active) == ["mesh-topk-unmerged"]

    def test_topk_routed_through_pack_format_quiet(self):
        active, _ = lint_snippet(
            """
            from jax import lax
            from predictionio_tpu.ops.topk import pack_batch

            def global_winners(scores, k):
                s, i = lax.top_k(scores, k)
                return pack_batch(s, i)
            """,
            "predictionio_tpu/ops/score_sharded.py",
        )
        assert active == []


# ---------------------------------------------------------------------------
# ISSUE 16 family: async-blocking-call
# ---------------------------------------------------------------------------


class TestAsyncBlockingRule:
    def test_direct_sleep_in_async_loop_fires(self):
        active, _ = lint_snippet(
            """
            import time

            async def run(self):
                while True:
                    self.tick()
                    time.sleep(1.0)
            """,
            "predictionio_tpu/fleet/autoscaler.py",
        )
        assert rule_ids(active) == ["async-blocking-call"]
        assert "time.sleep()" in active[0].message

    def test_asyncio_sleep_quiet(self):
        active, _ = lint_snippet(
            """
            import asyncio

            async def run(self):
                while True:
                    self.tick()
                    await asyncio.sleep(1.0)
            """,
            "predictionio_tpu/fleet/autoscaler.py",
        )
        assert active == []

    def test_transitive_blocking_callee_flagged_at_call_site(self, tmp_path):
        root = _write_tree(
            tmp_path,
            {
                "pkg/fleet/manager.py": """
                    from pkg.registry.store import save_state

                    async def run(self):
                        save_state("fleet.json")
                    """,
                "pkg/registry/store.py": """
                    import fcntl

                    def save_state(name):
                        with open(name, "wb") as fh:
                            fcntl.flock(fh, 2)
                            fh.write(b"{}")
                    """,
            },
        )
        report = analyze_paths([root])
        hits = [f for f in report.findings if f.rule == "async-blocking-call"]
        assert len(hits) == 1
        assert hits[0].path.endswith("manager.py")  # at the CALL site
        assert "save_state" in hits[0].message
        # names the primitive it bottoms out in, with its source location
        assert "fcntl.flock()" in hits[0].message or "open()" in hits[0].message
        assert "store.py:" in hits[0].message

    def test_executor_handoff_by_reference_quiet(self):
        # the sanctioned pattern: the blocking callable is an ARGUMENT,
        # not a call — no edge forms
        active, _ = lint_snippet(
            """
            import asyncio
            import time

            class Fleet:
                def drain(self):
                    time.sleep(5.0)

                async def run(self):
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(None, self.drain)
            """,
            "predictionio_tpu/fleet/supervisor.py",
        )
        assert active == []

    def test_nested_executor_delegate_quiet(self):
        # a def nested inside the async fn, handed to the executor: the
        # async-loop category deliberately does not flow into nested defs
        active, _ = lint_snippet(
            """
            import asyncio
            import time

            async def run(self):
                def work():
                    time.sleep(5.0)

                loop = asyncio.get_running_loop()
                await loop.run_in_executor(None, work)
            """,
            "predictionio_tpu/fleet/supervisor.py",
        )
        assert active == []

    def test_sync_code_outside_async_reach_quiet(self):
        # same module, but nothing async calls it: stop() is the
        # documented call-from-a-thread blocking path
        active, _ = lint_snippet(
            """
            import time

            def stop(self):
                time.sleep(0.05)
            """,
            "predictionio_tpu/fleet/supervisor.py",
        )
        assert active == []

    def test_requests_and_subprocess_fire(self):
        active, _ = lint_snippet(
            """
            import requests
            import subprocess

            async def probe(self, url):
                subprocess.run(["true"])
                return requests.get(url)
            """,
            "predictionio_tpu/data/api/eventserver.py",
        )
        assert sorted(rule_ids(active)) == [
            "async-blocking-call",
            "async-blocking-call",
        ]

    def test_suppressible_with_reason(self):
        active, suppressed = lint_snippet(
            """
            import time

            async def run(self):
                # pio-lint: disable=async-blocking-call -- startup-only settle wait, loop not serving yet
                time.sleep(0.01)
            """,
            "predictionio_tpu/fleet/supervisor.py",
        )
        assert active == []
        assert rule_ids(suppressed) == ["async-blocking-call"]


# ---------------------------------------------------------------------------
# ISSUE 16: suppression edge cases + stale detection
# ---------------------------------------------------------------------------


class TestSuppressionEdgeCases:
    def test_disable_file_with_multiple_rule_ids(self):
        active, suppressed = lint_snippet(
            """
            # pio-lint: disable-file=hostsync-serving-path,obs-unstructured-log -- generated adapter, reviewed by hand
            import numpy as np

            async def handle(pred):
                print("serving", pred)
                return np.asarray(pred)
            """,
            "predictionio_tpu/data/api/handlers.py",
        )
        assert active == []
        assert sorted(rule_ids(suppressed)) == [
            "hostsync-serving-path",
            "obs-unstructured-log",
        ]

    def test_standalone_comment_above_decorated_def(self):
        active, suppressed = lint_snippet(
            """
            import jax

            def compile_variants(configs):
                out = []
                for cfg in configs:
                    # pio-lint: disable=recompile-jit-in-loop -- one compile per config is the point here
                    @jax.jit
                    def step(x):
                        return x

                    out.append(step)
                return out
            """,
        )
        assert "recompile-jit-in-loop" not in rule_ids(active)
        assert "recompile-jit-in-loop" in rule_ids(suppressed)

    def test_stale_suppression_warns(self):
        active, _ = lint_snippet(
            """
            def fine(x):
                return x  # pio-lint: disable=hostsync-serving-path -- left over from a refactor
            """,
            "predictionio_tpu/data/api/handlers.py",
        )
        assert rule_ids(active) == ["suppression-stale"]
        assert active[0].severity == Severity.WARNING

    def test_used_suppression_not_stale(self):
        active, suppressed = lint_snippet(
            """
            import numpy as np

            async def handle(pred):
                # pio-lint: disable=hostsync-serving-path -- documented cold path
                return np.asarray(pred)
            """,
            "predictionio_tpu/data/api/handlers.py",
        )
        assert active == []
        assert rule_ids(suppressed) == ["hostsync-serving-path"]

    def test_blanket_suppression_never_stale_checked(self):
        active, _ = lint_snippet(
            """
            def fine(x):
                return x  # pio-lint: disable -- tool output, do not lint
            """,
        )
        assert active == []

    def test_docstring_mention_is_not_a_suppression_site(self):
        active, _ = lint_snippet(
            '''
            def helper(x):
                """Suppress with ``# pio-lint: disable=hostsync-serving-path -- why``."""
                return x
            ''',
        )
        assert active == []

    def test_stale_detection_skipped_under_rule_filter(self):
        # --rule runs a subset; a suppression for an un-run rule must not
        # be called stale
        active, _ = lint_snippet(
            """
            def fine(x):
                return x  # pio-lint: disable=hostsync-serving-path -- cold path
            """,
            "predictionio_tpu/data/api/handlers.py",
            config=LintConfig(enabled=frozenset({"tracer-python-branch"})),
        )
        assert active == []

    def test_stale_warning_is_itself_suppressible(self):
        active, suppressed = lint_snippet(
            """
            def fine(x):
                # pio-lint: disable=suppression-stale -- keeping the site through the refactor
                return x  # pio-lint: disable=hostsync-serving-path -- mid-refactor
            """,
            "predictionio_tpu/data/api/handlers.py",
        )
        assert active == []
        assert rule_ids(suppressed) == ["suppression-stale"]


# ---------------------------------------------------------------------------
# ISSUE 16: CLI — SARIF, --changed, --report-suppressions
# ---------------------------------------------------------------------------


class TestCLIOutputsAndScoping:
    def test_sarif_format(self, tmp_path, capsys):
        import json

        bad = tmp_path / "bad.py"
        bad.write_text(
            "import jax\n\n@jax.jit\ndef f(x):\n    return int(x)\n"
        )
        assert lint_main(["--format", "sarif", str(bad)]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "pio-lint"
        declared = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert "mesh-unknown-axis" in declared
        assert "async-blocking-call" in declared
        results = run["results"]
        assert results[0]["ruleId"] == "tracer-host-cast"
        assert results[0]["level"] == "error"
        region = results[0]["locations"][0]["physicalLocation"]["region"]
        assert region["startLine"] == 5

    def test_report_suppressions_inventory(self, tmp_path, capsys):
        f = tmp_path / "mod.py"
        f.write_text(
            "import jax\n\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return int(x)  # pio-lint: disable=tracer-host-cast -- benchmark harness\n"
            "def g(x):\n"
            "    return x  # pio-lint: disable=tracer-host-cast -- stale leftover\n"
        )
        assert lint_main(["--report-suppressions", str(f)]) == 0
        out = capsys.readouterr().out
        assert "benchmark harness" in out
        assert "stale leftover" in out
        assert "2 suppression site(s), 1 stale" in out

    def test_changed_scopes_reporting_not_the_graph(self, tmp_path, capsys, monkeypatch):
        import subprocess

        def git(*args):
            subprocess.run(
                ["git", *args],
                cwd=tmp_path,
                check=True,
                capture_output=True,
                env={
                    **os.environ,
                    "GIT_AUTHOR_NAME": "t",
                    "GIT_AUTHOR_EMAIL": "t@t",
                    "GIT_COMMITTER_NAME": "t",
                    "GIT_COMMITTER_EMAIL": "t@t",
                },
            )

        stale = tmp_path / "stale.py"
        stale.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return int(x)\n")
        fresh = tmp_path / "fresh.py"
        fresh.write_text("x = 1\n")
        git("init", "-q")
        git("add", "-A")
        git("commit", "-qm", "seed")
        fresh.write_text("import jax\n\n@jax.jit\ndef g(x):\n    return float(x)\n")
        monkeypatch.chdir(tmp_path)
        # both files have findings; only the modified one is reported
        assert lint_main([str(tmp_path), "--changed"]) == 1
        out = capsys.readouterr().out
        assert "fresh.py" in out
        assert "stale.py" not in out

    def test_changed_with_clean_tree_exits_zero(self, tmp_path, capsys, monkeypatch):
        import subprocess

        (tmp_path / "ok.py").write_text("x = 1\n")
        subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
        subprocess.run(["git", "add", "-A"], cwd=tmp_path, check=True)
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", "commit", "-qm", "s"],
            cwd=tmp_path,
            check=True,
            capture_output=True,
        )
        monkeypatch.chdir(tmp_path)
        assert lint_main([str(tmp_path), "--changed"]) == 0
        assert "no changed python files" in capsys.readouterr().out
