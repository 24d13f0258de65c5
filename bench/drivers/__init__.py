"""One module per traffic kind, found by the ``kind`` a traffic mix names
(``bench.harness.driver``).  A driver module gives

    drive(zoo, mix, seed, seconds, clock, tracer) -> harness.Window

which warms up every shape its traffic uses, starts ``tracer`` and runs
the measured window of ``seconds`` through the program's normal path,
lets what the window left open finish, and returns what the run reports
and what the check compares (``harness.Window``).
"""
