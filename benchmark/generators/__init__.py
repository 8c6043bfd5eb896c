"""Traffic generators: pure functions of (traffic file, seed, window length).

A traffic mix is a data file under ../traffic/ that names one of these
generators and gives its parameters; the program receives only what they
generate.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"{__name__}.{name}")
