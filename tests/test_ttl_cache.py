"""utils/ttl_cache.TTLCache: the bounded, expiring cache the serving paths
keep their event-store lookups in."""

from __future__ import annotations

import pytest


class TestTTLCache:
    def test_caches_within_ttl_and_counts(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=60)
        calls = []
        assert c.get_or_load("k", lambda: calls.append(1) or "v") == "v"
        assert c.get_or_load("k", lambda: calls.append(1) or "v2") == "v"
        assert len(calls) == 1 and c.hits == 1 and c.misses == 1

    def test_ttl_zero_bypasses(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=0)
        calls = []
        c.get_or_load("k", lambda: calls.append(1))
        c.get_or_load("k", lambda: calls.append(1))
        assert len(calls) == 2

    def test_expiry(self):
        import time

        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=0.03)
        c.get_or_load("k", lambda: "old")
        time.sleep(0.04)
        assert c.get_or_load("k", lambda: "new") == "new"

    def test_lru_bound(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=60, maxsize=2)
        for i in range(4):
            c.get_or_load(i, lambda i=i: i)
        assert len(c._entries) == 2

    def test_loader_exception_not_cached(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=60)
        with pytest.raises(RuntimeError):
            c.get_or_load("k", lambda: (_ for _ in ()).throw(RuntimeError("x")))
        # the failure must not poison the key: next load succeeds and caches
        assert c.get_or_load("k", lambda: "ok") == "ok"
        assert c.get_or_load("k", lambda: "other") == "ok"

    def test_invalidate(self):
        from predictionio_tpu.utils.ttl_cache import TTLCache

        c = TTLCache(ttl_s=60)
        c.get_or_load("k", lambda: "v1")
        c.invalidate("k")
        assert c.get_or_load("k", lambda: "v2") == "v2"
