"""The LaissezCloud benchmark harness (see ``bench/run.py``).

Everything that decides a number lives here, apart from the program:
the manifest and cell files, the tenant generator, the plain reference
of one market epoch, the comparison that decides ``correct``, the
profiler-trace reduction and the table of device peaks.
"""
