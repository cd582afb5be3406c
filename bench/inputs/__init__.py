"""The benchmark's own input generators, one module per generator.

A configuration names its generator (``"generator": "delaunay"``) and its
parameters, its own seed among them, and the harness loads
``inputs/<generator>.py``, whose ``make(**params)`` returns a dict of
NumPy arrays.  Generated inputs are cached under
``bench/.cache`` (see ``harness.load_inputs``); generation stands in for
reading the user's mesh file.
"""
