"""The benchmark's yardstick: what later PRs may not change.

``spec`` finds each cell's files by the names in ``BENCHMARK.json``;
``traffic`` is the one generator every traffic file feeds; ``loop`` drives
the program's step and times it; ``xplane`` and ``trace`` turn the
profiler's file into intervals and those into numbers; ``peaks`` and
``flops`` are the denominators; ``check`` decides ``correct``.
"""
