"""On-chip benchmark harness for the batched SpecBranch serving engine.

Everything that decides a number lives here, beside the data files it
reads: the traffic generator (``traffic``), the seeded weights
(``weights``), the wall-clock front end (``frontend``), the latency and
rate arithmetic (``stats``), the chip's peaks and the FLOP/byte counts
(``peaks``, ``flops``), the trace reduction (``trace``) and the check
against the plain float32 reference (``check``, ``references/``).
Configurations, cells, traffic mixes and metric readers are files found
by name (``registry``).
"""
