"""Round engines: how a cell builds and drives the program's runner."""
