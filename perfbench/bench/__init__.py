"""The chip benchmark's own code: loaders, traffic, weights, the serving
driver, the plain reference, the work model and the trace reduction.

Nothing here is imported by the program under test; the program is
imported from `<checkout>/src` only by `driver.py` (the system under test)
and never by `reference.py`.
"""
