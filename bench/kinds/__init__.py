"""Drivers, one per configuration ``kind``: how a cell of that kind makes
its inputs, what one timed call is, and what its check compares."""
