"""The benchmark's own code: nothing here imports the program under test
except the runner, which is the one place that drives it."""
