"""The program's side of each reference family (``layouts/<family>.py``):
the program's ``ModelConfig`` built from a configuration file, and the
published weights put into the program's parameter tree."""
