"""The program's side of each reference family (``layouts/<family>.py``):
the program's ``ModelConfig`` built from a configuration file
(``model_config``), and the conversion of a published leaf to the
program's layout (``to_program``), with which ``chipbench.weights`` fills
the program's whole parameter tree."""
