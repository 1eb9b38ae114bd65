"""Cohort samplers: the program's sampler and the reference's draw."""
