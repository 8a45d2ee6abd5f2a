"""The benchmark's own code, the same for every model family: traffic, load
generator, metric arithmetic, trace reduction, the seeded maker of weights,
the reference's loop and gap, peaks.  What knows an architecture is under
``families/``.  Nothing here is imported by the package, and only
``server.py`` (with each family's ``model.py``) imports the package."""
