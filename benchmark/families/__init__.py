"""One directory per model family: everything of the benchmark that knows an
architecture.  ``harness/cells.py:family`` finds ``families/<family>/`` by the
``family`` key of a configuration's file; benchmark/README.md ("A family")
states the four modules and the names each one gives."""
