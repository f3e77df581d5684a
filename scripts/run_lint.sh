#!/usr/bin/env bash
# Tier-1 lint gate: run the TPU-aware static analyzer over the package and
# examples. Exits nonzero on any unsuppressed error-severity finding.
# Usage: scripts/run_lint.sh [extra lint args...]
#        scripts/run_lint.sh --ci   # CI entry point: lint + CPU smokes + chaos
set -euo pipefail

repo_root="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$repo_root"

if [[ "${1:-}" == "--ci" ]]; then
  shift
  python -m predictionio_tpu.analysis.cli "$@"

  # --- lint artifacts (ISSUE 16): machine-readable SARIF for code-scanning
  #     upload, the git-scoped mode PR branches use (whole-program call
  #     graph, only changed files reported), and the suppression inventory
  #     (every pio-lint disable site with its reason; stale ones warn in
  #     the main pass above).
  python -m predictionio_tpu.analysis.cli --format sarif > /tmp/pio_lint.sarif
  python - <<'PYEOF'
import json
d = json.load(open("/tmp/pio_lint.sarif"))
assert d["version"] == "2.1.0", d["version"]
assert d["runs"][0]["tool"]["driver"]["name"] == "pio-lint"
print(f"sarif artifact: {len(d['runs'][0]['results'])} result(s), "
      f"{len(d['runs'][0]['tool']['driver']['rules'])} rules declared")
PYEOF
  python -m predictionio_tpu.analysis.cli --changed
  python -m predictionio_tpu.analysis.cli --report-suppressions \
    > /tmp/pio_lint_suppressions.txt
  echo "suppression inventory: $(tail -n 1 /tmp/pio_lint_suppressions.txt)"

  # --- capacity-planner self-check (pio doctor; docs/observability.md) ----
  # the planner must PASS a plan that fits ...
  ./pio doctor --capacity 100000 50000 16 --hbm-bytes 16GB \
    > /tmp/pio_doctor_fit.json
  # ... and EXIT NONZERO on one that exceeds the budget
  if ./pio doctor --capacity 10000000 1000000 128 --hbm-bytes 1MB \
      > /tmp/pio_doctor_over.json 2>/dev/null; then
    echo "pio doctor --capacity FAILED to flag an over-budget plan" >&2
    exit 1
  fi
  echo "capacity planner: fits within budget, trips over budget"

  # --- profiled CPU train smoke: the xray tiling contract end to end ------
  env JAX_PLATFORMS=cpu python - <<'PYEOF'
import numpy as np
from predictionio_tpu.obs import xray
from predictionio_tpu.ops.als import ALSConfig, als_train

rng = np.random.default_rng(0)
u = rng.integers(0, 300, 4000).astype(np.int32)
i = rng.integers(0, 200, 4000).astype(np.int32)
r = rng.normal(3.0, 1.0, 4000).astype(np.float32)
prof = xray.TrainProfile("ci-smoke")
with xray.use_profile(prof), prof.measure():
    als_train(u, i, r, 300, 200, ALSConfig(rank=8, iterations=3, chunk=1024))
pj = prof.finish().to_json_dict()
assert pj["steps"] == 3, pj["steps"]
ratio = pj["attributedS"] / pj["wallClockS"]
assert 0.9 <= ratio <= 1.001, f"tiling contract broken in CI: {ratio:.3f}"
assert pj["deviceS"] > 0.0
print(
    f"profiled train smoke: wall {pj['wallClockS']:.2f}s, "
    f"attributed {100*ratio:.1f}%, device frac {pj['deviceTimeFrac']:.2f}, "
    f"peak/dev {pj['memory']['peakBytesPerDevice']} B"
)
PYEOF

  # --- evalgrid smoke (ISSUE 15, docs/evaluation.md): 2 params x 2 folds
  #     on a tiny corpus with a REAL SIGKILL mid-grid — the resumed run
  #     must retrain zero finished cells (the durable-ledger contract)
  #     and stage the winner as a registry candidate carrying the grid
  #     evidence (scores table + ledger sha). The lint pass above already
  #     holds the scoring-path rails statically (serving-host-roundtrip /
  #     train-unaccounted-sync / eval-per-query-predict over tuning/).
  env JAX_PLATFORMS=cpu python scripts/evalgrid_smoke.py

  # --- lifecycle smoke (ISSUE 19, docs/lifecycle.md): one full
  #     self-driving loop with zero human commands after setup — a
  #     scheduled cadence trigger fires, the REAL eval grid runs on
  #     cpu-fallback workers and stages its winner as a registry
  #     CANDIDATE, the bake resolves to a promote, the controller warms
  #     the result cache over a real HTTP socket, and the episode closes
  #     PROMOTED with every transition on the telemetry ring and `pio
  #     lifecycle status` rendering the durable state file. The
  #     drift-triggered + SIGKILL-resume rails run in the chaos gate
  #     (tests/test_lifecycle.py e2e).
  env JAX_PLATFORMS=cpu python scripts/lifecycle_smoke.py

  # --- ANN smoke (ISSUE 10, docs/ann.md): build a small clustered index,
  #     serve a real engine through it via the registry attach path, and
  #     hold the two acceptance rails by measurement: recall@10 >= 0.95
  #     vs exact at <=10% of the corpus scored, and the exact path still
  #     answering when no index is pinned (the fallback default).
  env JAX_PLATFORMS=cpu python - <<'PYEOF'
import numpy as np, tempfile
from predictionio_tpu.ann import AnnConfig
from predictionio_tpu.ann import lifecycle
from predictionio_tpu.models.similarproduct.engine import (
    ALSAlgorithm, Query, SimilarModel,
)
from predictionio_tpu.registry import ArtifactStore, ModelManifest
from predictionio_tpu.workflow import model_io

rng = np.random.default_rng(0)
n, f = 8000, 16
modes = rng.normal(size=(48, f)); modes /= np.linalg.norm(modes, axis=1, keepdims=True)
vf = (modes[rng.integers(0, 48, n)] + 0.1 * rng.normal(size=(n, f))).astype(np.float32)
vf /= np.linalg.norm(vf, axis=1, keepdims=True)
vocab = [f"i{j}" for j in range(n)]
algo = ALSAlgorithm(None)
queries = [Query(items=(vocab[int(j)],), num=10) for j in rng.integers(0, n, 32)]

# exact-fallback rail: a model with NO index pinned answers exactly
plain = SimilarModel(vf.copy(), list(vocab), [None] * n)
exact = algo.predict_batch(plain, queries)
assert all(len(r.item_scores) == 10 for r in exact), "exact fallback broken"

with tempfile.TemporaryDirectory() as d:
    store = ArtifactStore(d)
    model = SimilarModel(vf.copy(), list(vocab), [None] * n)
    m = store.publish(
        ModelManifest(version="", engine_id="ann-smoke", engine_version="1",
                      engine_variant="v"),
        model_io.serialize_models([model]),
    )
    lifecycle.build_for_version(
        store, "ann-smoke", m.version, [model], AnnConfig(min_items=0), force=True
    )
    models = model_io.deserialize_models(store.load_blob("ann-smoke", m.version))
    serving = lifecycle.attach_from_registry(store, "ann-smoke", m.version, models)
    assert serving is not None, "index did not attach"
    ann = algo.predict_batch(models[0], queries)
    hits = total = 0
    for a, e in zip(ann, exact):
        ai = {s.item for s in a.item_scores}
        ei = [s.item for s in e.item_scores]
        hits += sum(1 for it in ei if it in ai)
        total += len(ei)
    recall = hits / total
    frac = serving.index.bucket_cap * serving.index.nprobe / n
    assert recall >= 0.95, f"ANN recall@10 {recall:.3f} < 0.95"
    assert frac <= 0.10, f"ANN candidate bound {frac:.3f} > 10% of corpus"
    print(f"ann smoke: recall@10 {recall:.3f} at <= {frac:.1%} of corpus scored, "
          f"exact fallback answers")
PYEOF

  # --- fleet smoke (ISSUEs 9+11, docs/fleet.md): 2 workers + gateway,
  #     kill one — the gateway must keep answering (ejection + failover),
  #     `pio top --fleet` must render from the federated /metrics, AND
  #     the flight recorder must capture the kill: an incident bundle
  #     with the dead worker's stderr tail and a merged gateway+replica
  #     trace (the incident-bundle smoke). The full kill-mid-ROLLOUT
  #     chaos stage lives in tests/test_fleet.py (run by the chaos gate
  #     below); this is the fast availability+evidence rail.
  env JAX_PLATFORMS=cpu python scripts/fleet_smoke.py
  echo "fleet smoke: gateway survives replica kill, pio top --fleet renders, incident bundle captured, scale-out/scale-in cycle clean"

  # --- multi-host smoke (ISSUE 17, docs/fleet.md §Multi-host): two fake-
  #     driver hosts, four workers, kill one host mid-traffic — zero
  #     failed queries, ONE host-death incident bundle carrying every
  #     dead worker's log tail (no per-worker crash bundles), pio top
  #     --fleet shows the HOST-DOWN census, and the host-aware scale-out
  #     path restores capacity on the survivor. The full kill-a-host
  #     chaos e2e (mid-ROLLOUT, lease steal from the dead holder) is the
  #     slow-marked stage in tests/test_hostrt.py, run by the chaos gate
  #     below.
  env JAX_PLATFORMS=cpu python scripts/hostrt_smoke.py
  echo "hostrt smoke: host death survived with zero failed queries, one host-death bundle, HOST-DOWN census rendered, capacity restored on survivor"

  # --- profile smoke (ISSUE 18, docs/observability.md §Profiling plane):
  #     one real CPU server with the plane on — `pio profile serve`
  #     captures a short device trace into a content-addressed bundle,
  #     the bundle lists/shows/exports through the CLI with the manifest
  #     model version matching the serving lane, /profile/stacks serves
  #     the always-on sampler's folded stacks, and `pio doctor
  #     --roofline` exits 0 with finite numbers for every bucket family.
  env JAX_PLATFORMS=cpu python scripts/profile_smoke.py

  # --- sequential+bandit smoke (ISSUE 20, docs/sequential.md +
  #     docs/bandit.md): ingest ordered sessions -> train the sequential
  #     engine THROUGH the real DataSource (find_after ordered reads) ->
  #     serve next-item queries through the fleet gateway into a real
  #     QueryServer with a Thompson bandit engaged on a staged candidate
  #     -> reward feedback events matched by trace id MOVE the candidate
  #     arm's posterior -> the reward verdict auto-promotes the winner
  #     with zero client-visible 5xx. The slow ingest->stream-fold-in->
  #     retire-loser e2e lives in tests/test_bandit.py (chaos gate).
  env JAX_PLATFORMS=cpu python scripts/sequential_smoke.py

  # chaos gate includes the observability suite (tests/test_obs.py):
  # counters moving under faults + trace propagation are CI-asserted
  exec "$repo_root/scripts/run_chaos.sh"
fi

exec python -m predictionio_tpu.analysis.cli "$@"
