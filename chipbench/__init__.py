"""chipbench — the chip benchmark's yardstick.

Everything that decides a number lives here: traffic generation, the
seeded weights, the plain float32 reference, FLOP and byte counts, the
table of peaks, the reduction from a profiler trace to metrics and the
comparison that decides ``correct``.  From the program under test
(``paddle_tpu``) it takes only the entry points a user calls.  See
README.md in this directory.
"""
