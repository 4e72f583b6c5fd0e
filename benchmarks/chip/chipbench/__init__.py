"""The chip benchmark's own code: loading of cells, the seeded weights, the
trace reduction, the work functions and the table of peaks."""
