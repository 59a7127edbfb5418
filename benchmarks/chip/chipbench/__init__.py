"""General code of the chip benchmark: manifest, drivers, trace reduction,
work counts, peaks and the comparison that decides ``correct``.

Everything that belongs to one configuration, traffic mix, model family
or per-layer metric lives in its own file beside this package
(``configs/``, ``traffic/``, ``limits/``, ``metrics/``, ``families/``,
``reference/``); nothing here names a cell.
"""
