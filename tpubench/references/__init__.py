"""Plain references that a family file imports where the equations do
not fit in it (``families/__init__.py``)."""
