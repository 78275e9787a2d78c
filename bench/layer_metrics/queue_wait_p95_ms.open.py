"""queue_wait_p95_ms.open: see bench/readers.py."""

from readers import queue_wait_p95_ms as read  # noqa: F401
