"""Interactive shell with preloaded stores (ref ``bin/pio-shell`` +
``python/pypio/shell.py``: a REPL with PEventStore/CleanupFunctions bound)."""

from __future__ import annotations

BANNER = """predictionio_tpu shell
Preloaded: storage, p_event_store, l_event_store, Event, DataMap, jax, jnp
Example: list(p_event_store.find("MyApp1", limit=5))
"""


def run_shell() -> None:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.store.event_store import LEventStore, PEventStore
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.workflow.cleanup import CleanupFunctions

    storage = Storage.instance()
    namespace = {
        "storage": storage,
        "p_event_store": PEventStore(storage),
        "l_event_store": LEventStore(storage),
        "Event": Event,
        "DataMap": DataMap,
        "CleanupFunctions": CleanupFunctions,
        "jax": jax,
        "jnp": jnp,
    }
    print(BANNER)
    try:
        from IPython import start_ipython

        start_ipython(argv=["--no-banner"], user_ns=namespace)
    except ImportError:
        import code

        code.interact(banner="", local=namespace)
    finally:
        CleanupFunctions.run()
