"""Small helpers shared by the program's layers."""
