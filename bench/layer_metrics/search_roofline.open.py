"""search_roofline.open: see bench/readers.py."""

from readers import search_roofline as read  # noqa: F401
