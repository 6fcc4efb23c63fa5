"""The chip benchmark's own code: loaders, traffic, the serving driver, the
work and trace reductions, and what the model modules
(`perfbench/models/<model_type>.py`) share: the int4 weight scheme, the
reference's arithmetic, the per-call work formulas.

Nothing here is imported by the program under test; the program is
imported from `<checkout>/src` only by `driver.py` and by a model module's
`servable` (the system under test), never by a reference.
"""
