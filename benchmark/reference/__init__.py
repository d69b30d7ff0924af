"""The plain reference: numpy float64 over the arrays the seed makes.
It imports nothing of the program and takes nothing the program made."""
