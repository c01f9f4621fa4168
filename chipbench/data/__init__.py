"""Data generators copied from the program, so the yardstick stays fixed."""
