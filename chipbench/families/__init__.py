"""Model families: weights, plain reference and FLOPs per family."""
