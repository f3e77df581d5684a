"""The ``pio_seq_*`` metric families (docs/observability.md): the stream
trainer's (``SeqInstruments``) and the backbone scorers' (``olmoe``,
``kimi_linear``, ``sdar``: ``BackboneInstruments``, with ``pio_moe_*``).

``SeqInstruments`` is registered eagerly (AnnInstruments discipline): the family exists at zero
from process start so scrapers and the docs metrics-contract test see it
before the first session folds in. The stream pipeline binds it to the
:class:`~predictionio_tpu.stream.trainers.SequentialStreamTrainer` via its
``instruments`` kwarg."""

from __future__ import annotations

from predictionio_tpu.obs.metrics import MetricsRegistry


class SeqInstruments:
    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = registry or MetricsRegistry()
        r = self.registry
        self.transitions = r.counter(
            "pio_seq_transitions_total",
            "session transitions (prev item -> next item) absorbed by the "
            "streaming sequential trainer",
        )
        self.items_touched = r.counter(
            "pio_seq_items_touched_total",
            "items whose outgoing transition row changed, summed over "
            "absorbed micro-batches",
        )
        self.states = r.gauge(
            "pio_seq_states",
            "states (items) in the last published transition matrix",
        )
        self.pairs = r.gauge(
            "pio_seq_pairs",
            "distinct (from, to) transition pairs in the last published "
            "matrix",
        )
        self.sessions = r.gauge(
            "pio_seq_sessions",
            "live per-user session cursors the stream trainer tracks "
            "(bounded by its max_users)",
        )
        self.snapshots = r.counter(
            "pio_seq_snapshots_total",
            "stream snapshots rebuilt into a servable SequentialModel",
        )

    def on_absorb(self, transitions: int, items_touched: int) -> None:
        self.transitions.inc(float(transitions))
        self.items_touched.inc(float(items_touched))

    def on_snapshot(self, states: int, pairs: int, sessions: int) -> None:
        self.states.set(float(states))
        self.pairs.set(float(pairs))
        self.sessions.set(float(sessions))
        self.snapshots.inc()


class BackboneInstruments:
    """What a backbone scorer (``olmoe``, ``kimi_linear``, ``lfm2``, ``sdar``, ``kanana``) launched, counted
    where it happens (``backbone.BackboneAlgorithm``). The expert counters are
    over the experts the chip HOLDS. An algorithm starts with a registry of its
    own; a query server that serves it hands over its registry through
    ``register_metrics``, so two deployments in one process count apart."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = registry or MetricsRegistry()
        r = self.registry
        self.tokens = r.counter(
            "pio_seq_tokens_total",
            "tokens of launched session programs: kind=real are session "
            "items, kind=padded is what the device computed (whole streams)",
            labelnames=("kind",),
        )
        self.programs = r.counter(
            "pio_seq_programs_total",
            "session programs launched, by the length of their token stream",
            labelnames=("bucket",),
        )
        self.rows = r.counter(
            "pio_seq_rows_total",
            "rows of launched session programs (a token stream is one row; over "
            "pio_seq_programs_total: the streams a program stacks), by the "
            "stream's length",
            labelnames=("bucket",),
        )
        self.sessions = r.counter(
            "pio_seq_sessions_total",
            "sessions packed into launched session programs, by the stream's "
            "length (over pio_seq_rows_total: sessions a stream)",
            labelnames=("bucket",),
        )
        self.stage_seconds = r.counter(
            "pio_seq_stage_seconds_total",
            "seconds the dispatch thread spent looking sessions up and "
            "packing them into token streams (once a batch)",
        )
        self.batches = r.counter(
            "pio_seq_batches_total", "batches the session scorer staged"
        )
        self.passes = r.counter(
            "pio_seq_passes_total",
            "passes of a generating backbone over a batch's sessions and its "
            "cache: kind=denoise fixed a position of some session's block, "
            "kind=commit only appended clean blocks' keys and values, "
            "kind=decode chose one more item a session (one position a step)",
            labelnames=("kind",),
        )
        self.blocks = r.counter(
            "pio_seq_blocks_total", "blocks of items generated, summed over sessions"
        )
        self.generated_items = r.counter(
            "pio_seq_generated_items_total", "items generated into answers"
        )
        self.cache_bytes = r.counter(
            "pio_seq_cache_bytes_total",
            "bytes of keys and values (sdar) or of latents (kanana) a batch's "
            "cache held for its sessions (its streams and what was generated), "
            "summed over batches",
        )
        self.expert_tokens_max = r.counter(
            "pio_moe_expert_tokens_max_total",
            "copies of REAL tokens (no padding) the busiest expert got, "
            "summed over layers and programs",
        )
        self.expert_tokens_mean = r.counter(
            "pio_moe_expert_tokens_mean_total",
            "copies of real tokens an even split would give each expert, "
            "summed over layers and programs",
        )
        self.experts_reached = r.counter(
            "pio_moe_experts_reached_total",
            "experts that got a copy of a REAL row, summed over the layers of "
            "a generating backbone's passes: whose matrices a pass had to read",
        )
        self.experts_offered = r.counter(
            "pio_moe_experts_offered_total",
            "experts the same layers and passes hold (over it "
            "pio_moe_experts_reached_total is the share reached)",
        )
        self.copies = r.counter(
            "pio_moe_copies_total",
            "copies of REAL tokens the routers sent out, by whether the "
            "expert is one the chip holds (where=held: computed here) or "
            "another chip's (where=absent: left out)",
            labelnames=("where",),
        )

        self.held_blocks = r.counter(
            "pio_moe_held_blocks_total",
            "sparse layers of launched programs that lay out the held copies "
            "alone (ops/moe.held_expert_ffn), by whether those fitted the "
            "compact block (rounds=one) or overflowed it and ran further "
            "rounds over the rest (rounds=more)",
            labelnames=("rounds",),
        )

    def on_generation(
        self, items: int, cache_bytes: int, blocks: int = 0, **passes: int
    ) -> None:
        """One group of sessions answered by generation; ``passes`` by kind:
        ``sdar``'s ``denoise`` and ``commit`` over ``blocks``, ``kanana``'s
        ``decode`` steps."""
        for kind, count in passes.items():
            self.passes.inc(float(count), kind=kind)
        self.blocks.inc(float(blocks))
        self.generated_items.inc(float(items))
        self.cache_bytes.inc(float(cache_bytes))

    def on_copies(self, held: int, absent: int) -> None:
        self.copies.inc(float(held), where="held")
        self.copies.inc(float(absent), where="absent")

    def on_held_blocks(self, one: int, more: int) -> None:
        self.held_blocks.inc(float(one), rounds="one")
        self.held_blocks.inc(float(more), rounds="more")

    def on_experts_reached(self, reached: int, offered: int) -> None:
        """One group's passes, counted with its answer's fetch."""
        self.experts_reached.inc(float(reached))
        self.experts_offered.inc(float(offered))

    def on_stage(self, seconds: float) -> None:
        self.stage_seconds.inc(seconds)
        self.batches.inc()

    def on_launch(self, bucket: int, rows: int, real_tokens: int, sessions: int) -> None:
        self.tokens.inc(float(real_tokens), kind="real")
        self.tokens.inc(float(rows * bucket), kind="padded")
        self.programs.inc(bucket=str(bucket))
        self.rows.inc(float(rows), bucket=str(bucket))
        self.sessions.inc(float(sessions), bucket=str(bucket))

    def on_expert_load(self, busiest: int, even: float) -> None:
        """One program's layers, summed: the copies of real tokens its
        busiest expert got in each, and what an even split gives each."""
        self.expert_tokens_max.inc(float(busiest))
        self.expert_tokens_mean.inc(even)
