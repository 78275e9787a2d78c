"""device_idle.random: see bench/readers.py."""

from readers import device_idle as read  # noqa: F401
