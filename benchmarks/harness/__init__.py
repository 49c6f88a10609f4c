"""The benchmark's yardstick: everything here is the benchmark's own copy, so a
later change to the program cannot move a number by editing shared code."""
