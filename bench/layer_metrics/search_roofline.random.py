"""search_roofline.random: see bench/readers.py."""

from readers import search_roofline as read  # noqa: F401
