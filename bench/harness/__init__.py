"""The benchmark harness: everything the cells share.

Nothing here belongs to one configuration, traffic mix, cell or metric;
those are data files and readers found by name (see ``spec.py``).
"""
