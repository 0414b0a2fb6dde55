"""CPU tests of the benchmark: reference, control, trace reduction, counts
and the check's faults, at sizes a test run can hold."""
