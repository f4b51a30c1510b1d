"""Plain references, one module per architecture, found by the
`reference` key of a configuration file. They import nothing of the
program under test."""
