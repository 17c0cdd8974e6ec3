"""The chip benchmark: one cell (configuration x traffic) per run.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one metric
lives in a file of its own and is found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json``   sizes as run, source, ``reduced``, ``assumed``
* ``traffic/<mix>.json``      generator parameters and the driver that runs it
* ``drivers/<driver>.py``     how a kind of cell is driven (``serve``)
* ``reference/<model>.py``    seeded weights and the plain float32 reference
* ``costs/<model>.py``        operations and bytes from shapes
* ``metrics/<metric>.py``     the reduction from a run record to one number
* ``peaks.json``              the chip's peaks, keyed by ``device_kind``

The program under test comes in through ``src/`` (``repro``); nothing here
is imported by it.
"""
