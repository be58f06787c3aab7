"""The benchmark: the yardstick for paddle_tpu on the chip (see PERF.md).

Everything a later PR may not move lives here: traffic generation, the
reduction from traces to metrics, the table of peaks, the operation and byte
counts, each configuration's plain float32 reference and the comparison that
decides `correct`. From the program it takes the system under test only.
"""
