"""One module per model family: how it builds its logic, store and batches
through the program's public entry points, which rows a batch touches, and
the bytes its step's gathers and scatters must move.  The runner finds a
family by the ``family`` key of a configuration file."""
