"""The chip benchmark's own library: files, data, reference steps, trace
reduction.  Nothing here imports the program under test (``src/repro``);
``bench/run.py`` is the one place that does."""
