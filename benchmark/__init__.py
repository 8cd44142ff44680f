"""The benchmark: the yardstick later PRs are held to. See PERF.md.

Everything that decides a number lives here (traffic generation, the
reduction from traces and host clocks to metrics, the table of peaks, the
FLOP arithmetic, the plain float32 references, the comparison that decides
``correct``). From the program it takes only the system under test.
"""
