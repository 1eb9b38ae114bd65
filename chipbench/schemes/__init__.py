"""Schemes: the program's scheme and the reference's upload and control checks."""
