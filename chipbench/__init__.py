"""A benchmark of the federated round engine on the chip; see run.py."""
