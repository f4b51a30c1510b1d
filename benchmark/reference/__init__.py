"""Plain references, one module per family, handed out by the family's
`reference()`. They import nothing of the program under test."""
