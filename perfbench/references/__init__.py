"""Plain references, one module per architecture family, named by the
``reference`` key of a configuration file. A reference imports nothing of
the program and makes its weights again from the seed (``weights.py``)."""
