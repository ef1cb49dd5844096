"""The on-chip benchmark of the served RPQ path.

``run.py`` (one directory up) is the command; the modules here are its
yardstick: ``spec`` finds a cell's files by name, ``traffic`` is the one
load generator, ``reference`` the plain reference that decides
``correct``, ``xplane`` the reduction of profiler traces, ``peaks`` the
table of chip peaks, and ``harness`` the run itself.
"""
