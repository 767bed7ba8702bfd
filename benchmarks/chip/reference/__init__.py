"""The plain float32 reference the benchmark's ``correct`` is decided
against (see ``decoder.py``)."""
