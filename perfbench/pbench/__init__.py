"""The benchmark's own yardstick: nothing here is imported by the program."""
