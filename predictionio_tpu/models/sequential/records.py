"""The sequential engine's records: what a query, an answer and a training
read ARE, and the base of a backbone's parameters. They are needed on every
level of the package (``engine.py``, ``backbone.py`` and the backbones'
modules below it), so they lie beside the lowest and this module imports no
JAX of its own and nothing of the package."""

from __future__ import annotations

import dataclasses
import sys
from typing import Any

import numpy as np

from predictionio_tpu.controller import Params, SanityCheck


@dataclasses.dataclass(frozen=True)
class Query:
    """``recentItems`` is the caller-supplied session tail (most recent
    LAST); when absent, the model's stored last-item for ``user`` answers
    (ref e-commerce template's recent-event lookup)."""

    user: str | None = None
    recent_items: tuple[str, ...] = ()
    num: int = 10

    @staticmethod
    def from_json_dict(d: dict[str, Any]) -> "Query":
        return Query(
            user=d.get("user"),
            recent_items=tuple(d.get("recentItems") or ()),
            num=int(d.get("num", 10)),
        )


@dataclasses.dataclass(frozen=True)
class ItemScore:
    """``step``, where an answer was GENERATED block by block: the 0-based
    denoise pass of the item's block that fixed it; ``score`` is then the
    log-probability it was fixed at."""

    item: str
    score: float
    step: int | None = None

    def to_json_dict(self) -> dict[str, Any]:
        out = {"item": self.item, "score": self.score}
        return out if self.step is None else {**out, "step": self.step}


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...]

    def to_json_dict(self) -> dict[str, Any]:
        return {"itemScores": [s.to_json_dict() for s in self.item_scores]}


@dataclasses.dataclass(frozen=True)
class ActualResult:
    """The user's true continuation (ordered) for eval folds."""

    items: tuple[str, ...]


@dataclasses.dataclass
class TrainingData(SanityCheck):
    """Ordered per-user sessions, dictionary-encoded: ``sequences[i]`` is
    user ``users[i]``'s item-index sequence in event order."""

    users: list[str]
    sequences: list[np.ndarray]
    item_vocab: list[str]

    def sanity_check(self) -> None:
        if len(self.users) != len(self.sequences):
            raise ValueError("users/sequences length mismatch")
        if not any(len(s) >= 2 for s in self.sequences):
            raise ValueError(
                "no session with >= 2 events — nothing to learn transitions from"
            )


class BackboneParams(Params):
    """A backbone's parameters: the published ``config.json`` key for key (a
    variant file carries them verbatim; the defaults are the published values,
    so a variant may leave a key out), the seed the weights are drawn from and
    whatever the backbone's class adds. The class stands in the backbone's
    module beside the ``Config`` it feeds.

    ``ONE_ANSWER``: the keys the program has one answer for, each with that
    answer (a function of the parameters where it is another key's value).
    They are refused at any other value rather than ignored."""

    ONE_ANSWER: dict = {}

    def derived(self) -> dict:
        """The ``Config``'s fields that are NOT a parameter of the same name:
        the renamed, the defaulted from another key, the converted. It may
        refuse what ``ONE_ANSWER`` cannot say."""
        return {}

    def config(self):
        """The module's ``Config``: its fields from the parameters OF THE SAME
        NAME, and ``derived()``'s."""
        module = sys.modules[type(self).__module__]
        backbone = module.__name__.rpartition(".")[2]
        for key, only in self.ONE_ANSWER.items():
            only, mine = only(self) if callable(only) else only, getattr(self, key)
            if (tuple(mine) if isinstance(mine, list) else mine) != only:
                raise ValueError(f"{backbone}: {key}={mine!r} is not implemented (only {only!r})")
        fields = dataclasses.fields(module.Config)
        named = {f.name: getattr(self, f.name) for f in fields if hasattr(self, f.name)}
        return module.Config(**{**named, **self.derived()})
