"""The benchmark's own yardstick: generator, window accounting, trace
reduction, peaks. Nothing here imports the program."""
