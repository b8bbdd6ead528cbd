"""Plain NumPy reference of the suggest step, layer by layer; imports
nothing of the program under test."""
