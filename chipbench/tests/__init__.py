"""CPU tests of the chip benchmark: layout, trace reduction, references,
the control and planted faults."""
