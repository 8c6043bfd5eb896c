"""The benchmark: data files plus a small harness (see README.md here).

Nothing under this directory is imported by the package; the harness takes
from the program only the system under test and its counters.
"""
